"""Architectures, one module each, found by name: `arch/<name>.py`, where
<name> is a configuration file's `"arch"` key (`dense_decoder` where the
file has none). A module is everything the benchmark knows of its
architecture, read from the configuration's own keys; nothing outside it
reads them. It provides:

* `model_config(c)`: the program's `ModelConfig` for configuration `c`;
* `init_leaf(name, shape, z)`: a weight leaf, from its path in the
  parameter tree ("layers/attn/wq"), its stacked shape and its
  standard-normal draw `z` of that shape (float32; cast by the caller);
* `prefill_key(engine, plen, skip)`: the prefill an admission of a
  `plen`-token prompt with `skip` cached tokens dispatches;
* `served_logits(params, tokens, n_prompt, *, model, quant, chunk,
  requant, rows, mode)`: the plain reference (see `reference.py`);
* `matmul_params(m)`: (weights of the layers, weights of the LM head)
  that one token multiplies through;
* `attention_layers(m)`: one (q heads, kv heads, head_dim) per layer
  whose K/V live in the pool, in the order of the quantizer's schedule
  (its length is the schedule's, in the program and the reference);
* `shrink(c)`: `c` at the widths of the CPU rehearsals.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

HERE = Path(__file__).resolve().parent
DEFAULT = "dense_decoder"


@functools.cache
def _load(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_arch_" + path.stem.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load(name: str):
    """The module `arch/<name>.py` (loaded once a process, so that its
    jitted reference compiles once)."""
    path = HERE / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no architecture {name!r}: {path} does not exist")
    return _load(path)


def of(config: dict):
    """The architecture module of a configuration file's dict."""
    return load(config.get("arch", DEFAULT))
