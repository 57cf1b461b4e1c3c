"""Measured autotuner for the quantized-attention kernel's token block.

`default_block_t` derives the token-block size from a VMEM budget — a
*model-based* default, and a model can drift from the clock. This module
closes the loop: it times the real kernel over candidate `block_t` values
on the caller's exact geometry and caches the winner in a JSON file keyed
by (geometry, backend, platform), so the measurement is paid once per
machine, not per process.

`block_t` is the contiguous kernel's token block, which runs the paged
kernel over `block_t`-token pages — so the best page-sized candidate is
also reported as `page_size` for `SchedulerConfig`.

All candidates produce the same attention outputs up to the online
softmax's block order, so the tuner is pure perf policy: `tuned_backend`
applies a cached entry to a `QuantPallasBackend` without re-measuring, and
`tools/autotune.py` is the CLI for measuring / printing the cache. The
serving path never reads this cache.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache import kvcache
from repro.configs.base import ModelConfig
from repro.core.quantizer import KVQuantizer
from repro.kernels.qattn import ops as qattn_ops

#: block_t candidates (clamped to the measured context); page-sized
#: candidates double as page_size proposals for the paged scheduler
DEFAULT_BLOCK_TS = (128, 256, 512, 1024)
DEFAULT_PAGE_CANDIDATES = (128, 256, 512)


def default_cache_path() -> Path:
    """JSON cache location: $REPRO_AUTOTUNE_CACHE or ~/.cache/repro/."""
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return Path(env)
    return Path.home() / ".cache" / "repro" / "qattn_autotune.json"


def cache_key(cfg: ModelConfig, qz: KVQuantizer) -> str:
    """Per-(geometry, backend, platform) identity of one tuning entry.

    Everything that changes the kernel's inner loop is in the key: head
    geometry (d_pad / pairs set the tile), storage + index width (the
    unpack work), norm configs (the dequant arithmetic), and the JAX
    platform (the lowering target the timings are valid for).
    """
    qc = qz.config
    return "|".join([
        jax.default_backend(),
        f"nkv{cfg.num_kv_heads}", f"g{cfg.q_per_kv}", f"d{cfg.head_dim}",
        qc.resolved_storage, f"iw{qc.index_width}",
        f"k{qc.k_norm.describe()}", f"v{qc.v_norm.describe()}",
    ])


def load_cache(path: Path | None = None) -> dict:
    path = path or default_cache_path()
    if path.exists():
        return json.loads(path.read_text())
    return {}


def save_cache(entries: dict, path: Path | None = None) -> Path:
    path = path or default_cache_path()
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(entries, indent=2, sort_keys=True) + "\n")
    return path


def _filled_cache(cfg: ModelConfig, qz: KVQuantizer, t: int, rng):
    shape = (1, 1, t, cfg.num_kv_heads, cfg.head_dim)  # (L=1, B=1, ...)
    k = jnp.asarray(rng.normal(size=shape), jnp.float32)
    v = jnp.asarray(rng.normal(size=shape), jnp.float32)
    nk, nv = qz.layer_bins()
    return kvcache.QuantKVCache(
        k=qz.encode(k, int(nk[0]), qz.config.k_norm),
        v=qz.encode(v, int(nv[0]), qz.config.v_norm),
        lengths=jnp.full((1,), t, jnp.int32))


def measure_attend(cfg: ModelConfig, qz: KVQuantizer, *, t: int,
                   block_t: int, reps: int, interpret: bool, rng) -> float:
    """Steady-state milliseconds per contiguous-kernel attend call at the
    given knob setting (compile excluded: one warmup call, then the
    median of `reps` timed calls)."""
    cache = _filled_cache(cfg, qz, t, rng)
    layer_k = jax.tree.map(lambda a: a[0], cache.k)
    layer_v = jax.tree.map(lambda a: a[0], cache.v)
    nk, nv = qz.layer_bins()
    nk0, nv0 = int(np.asarray(nk)[0]), int(np.asarray(nv)[0])
    q = jnp.asarray(rng.normal(size=(1, 1, cfg.num_heads, cfg.head_dim)),
                    jnp.float32)

    @jax.jit
    def fn(q, lk, lv, lengths):
        return qattn_ops.attend_quant_cache_op(
            q, lk, lv, nk0, nv0, lengths, cfg, qz,
            interpret=interpret, block_t=block_t)

    fn(q, layer_k, layer_v, cache.lengths).block_until_ready()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(q, layer_k, layer_v, cache.lengths).block_until_ready()
        times.append(time.perf_counter() - t0)
    return float(np.median(times)) * 1e3


def autotune(cfg: ModelConfig, qz: KVQuantizer, *, interpret: bool,
             t: int = 1024, reps: int = 3, block_ts=None,
             cache_path: Path | None = None, refresh: bool = False,
             seed: int = 0) -> dict:
    """Measure the candidate block sizes and cache the winner.

    Returns the cache entry: {block_t, page_size, attend_ms, t,
    measured: {"bt=..": ms}}. A cached entry for the same key is returned
    as-is unless `refresh`.
    """
    key = cache_key(cfg, qz)
    entries = load_cache(cache_path)
    if not refresh and key in entries:
        return entries[key]
    block_ts = tuple(b for b in (block_ts or DEFAULT_BLOCK_TS) if b <= t)
    rng = np.random.default_rng(seed)
    measured = {f"bt={bt}": measure_attend(cfg, qz, t=t, block_t=bt,
                                           reps=reps, interpret=interpret,
                                           rng=rng)
                for bt in block_ts}
    bt_best = min(block_ts, key=lambda b: measured[f"bt={b}"])
    ms_best = measured[f"bt={bt_best}"]
    # page_size proposal: best block among page-sized candidates (a paged
    # grid step is the same tile of work)
    page_cands = [b for b in DEFAULT_PAGE_CANDIDATES if f"bt={b}" in measured]
    page_size = (min(page_cands, key=lambda b: measured[f"bt={b}"])
                 if page_cands else bt_best)
    entry = {
        "block_t": bt_best, "page_size": page_size,
        "attend_ms": ms_best, "t": t, "reps": reps,
        "interpret": interpret, "measured": measured,
    }
    entries[key] = entry
    save_cache(entries, cache_path)
    return entry


def best(cfg: ModelConfig, qz: KVQuantizer,
         cache_path: Path | None = None) -> dict | None:
    """Cached entry for this geometry, or None — never measures."""
    return load_cache(cache_path).get(cache_key(cfg, qz))


def tuned_backend(backend, cache_path: Path | None = None):
    """Apply a cached tuning entry to a QuantPallasBackend (block_t),
    or return the backend unchanged when nothing is cached.
    Never measures — the cache is populated by `autotune` /
    `tools/autotune.py --refresh`."""
    entry = best(backend.cfg, backend.quantizer, cache_path)
    if entry is None:
        return backend
    return dataclasses.replace(backend, block_t=int(entry["block_t"]))
