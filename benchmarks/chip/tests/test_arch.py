"""Architectures are modules found by name (`arch/<arch>.py`): the default
and an unknown name, a new architecture added as new files only and run
end to end, the weights handed leaf by leaf to the module, and the two
cells' weights as they were before the modules existed."""
import hashlib
import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import arch
import conftest
import harness
import roofline

SEED = 2**31 + 11

#: sha256 of the shrunk cells' weights at SEED, every leaf's bytes in
#: tree order, as `make_weights` made them before it called `init_leaf`
WEIGHTS = {
    "qwen3-0.6b.doc-decode":
        "8092b30d76c04274847a0ebb09f3b56ba0a238d9a3a46f44031357bef73c241e",
    "deepseek-7b-l8.reason-decode":
        "b527713222d8e2e92cebf37f65c99069adeb981e194e23b289a521565c6fff67",
}


def digest(tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(tree):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def test_absent_key_is_the_dense_decoder():
    c = harness._json(harness.HERE / "configs" / "qwen3-0.6b.json")
    assert "arch" not in c
    dense = arch.of(c)
    assert dense is arch.of(dict(c, arch="dense_decoder"))
    assert dense is arch.load("dense_decoder")
    assert dense.__file__ == str(arch.HERE / "dense_decoder.py")
    for name in conftest.CELLS:
        assert harness.load_cell(name).arch is dense


def test_unknown_arch_names_the_file():
    with pytest.raises(KeyError, match=r"arch/no_such_arch\.py"):
        arch.of({"arch": "no_such_arch"})


@pytest.mark.parametrize("name", conftest.CELLS)
def test_cell_weights_unchanged(name):
    cell = conftest.shrink(harness.load_cell(name))
    params = harness.make_weights(SEED, cell.arch.model_config(cell.config),
                                  cell.arch.init_leaf)
    assert digest(params) == WEIGHTS[name]


@pytest.mark.parametrize("arch_id", ["zamba2-2.7b", "granite-moe-3b-a800m",
                                     "xlstm-350m"])
def test_every_leaf_goes_to_init_leaf(arch_id):
    """The program's reduced hybrid_ssm, MoE and xLSTM models: each leaf
    reaches `init_leaf` once, in tree order, by its path and stacked
    shape, and holds what `init_leaf` gave."""
    from repro.configs import registry
    from repro.models import transformer

    cfg = registry.get_reduced_config(arch_id)
    seen = []

    def init_leaf(name, shape, z):
        assert z.shape == shape and z.dtype == jnp.float32
        seen.append((name, shape))
        return jnp.full(shape, len(seen), jnp.float32)

    params = harness.make_weights(SEED, cfg, init_leaf)
    shapes, _ = transformer.abstract_params(cfg)
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    assert seen == [("/".join(str(k.key) for k in path), sds.shape)
                    for path, sds in flat]
    assert {n.split("/")[-1] for n, _ in seen} >= {
        "zamba2-2.7b": {"a_log", "dt_bias", "d_skip", "conv_w"},
        "granite-moe-3b-a800m": {"router", "w_gate"},
        "xlstm-350m": {"gate_bias", "r", "w_gates"},
    }[arch_id]
    for i, leaf in enumerate(jax.tree_util.tree_leaves(params)):
        assert np.all(np.asarray(leaf, np.float32) == i + 1)


def test_new_arch_is_found_by_name_and_rehearses(tmp_path, monkeypatch):
    """A copy of the benchmark gains an architecture (the test-only
    `tests/data/arch/renamed_decoder.py`, whose configuration names its
    sizes in keys no other module knows), a configuration, a limit file
    and a cell; no existing file changes, and a CPU rehearsal of the cell
    serves correctly through the new module."""
    bench = tmp_path / "benchmarks" / "chip"
    shutil.copytree(harness.HERE, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "_trace"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}

    shutil.copy(harness.HERE / "tests" / "data" / "arch" /
                "renamed_decoder.py", bench / "arch")
    dense = harness._json(harness.HERE / "configs" / "qwen3-0.6b.json")
    names = {"num_hidden_layers": "n_layer", "hidden_size": "n_embd",
             "intermediate_size": "n_inner",
             "num_attention_heads": "n_head",
             "num_key_value_heads": "n_kv_head", "head_dim": "n_head_dim",
             "vocab_size": "n_vocab"}
    renamed = {names.get(k, k): v for k, v in dense.items()}
    renamed.update(name="qwen3-renamed", arch="renamed_decoder")
    (bench / "configs" / "qwen3-renamed.json").write_text(
        json.dumps(renamed))
    (bench / "cells" / "qwen3-renamed.doc-decode.json").write_text(
        json.dumps({"logit_gap": 0.025}))
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "qwen3-renamed.doc-decode",
                              "config": "qwen3-renamed",
                              "traffic": "doc-decode", "chips": 1,
                              "why": "test"})

    monkeypatch.setattr(harness, "HERE", bench)
    monkeypatch.setattr(arch, "HERE", bench / "arch")
    cell = harness.load_cell("qwen3-renamed.doc-decode", spec)
    assert cell.arch.__file__ == str(bench / "arch" / "renamed_decoder.py")
    assert not set(names) & set(cell.config)
    # the yardstick counts the same work as for the dense file
    assert roofline.model_flops(renamed, 999, 9, [(300, 128)]) == \
        roofline.model_flops(dense, 999, 9, [(300, 128)])
    assert roofline.qattn_work(renamed, renamed["serving"]["kv_quant"], 9,
                               999) == \
        roofline.qattn_work(dense, dense["serving"]["kv_quant"], 9, 999)

    out = harness.run_cell(conftest.shrink(cell), SEED, 2.0, False,
                           interpret=True, log=lambda m: None)
    assert out.window.completed and out.window.compiles == 0
    assert harness.verdict(out.checks), out.checks
    assert {p: p.read_bytes() for p in before} == before
