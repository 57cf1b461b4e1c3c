"""The quantized-attention kernels compile for a TPU v5e.

Interpret mode cannot show what the TPU's compiler refuses (block shapes
it cannot tile, ops it cannot lower), so these tests compile the kernels
with interpret=False for a *described* v5e chip — the TPU compiler is
installed, no chip is attached, nothing runs. Each compile takes about a
second and must contain the Mosaic kernel (`tpu_custom_call`), i.e. the
program calls the compiled kernel and not an interpreted body.

Geometries: head_dim 64 with 8 kv heads and 2 q heads per kv head under
the early-boost schedule (8-bit index width); head_dim 128 with the
uniform schedule (7-bit codes that straddle words); and the benchmark's
two models at their published widths under the early-boost schedule:
qwen3-0.6b (head_dim 128, 8 kv heads, 2 q heads per kv head) and
deepseek-llm-7b (head_dim 128, 32 kv heads, MHA). All are bit-packed with
8-bit K norms and 4-bit log V norms. At 128-token pages the paged
kernels take 2 kv heads per grid step, at 16-token pages all 8 or 32, so
these compiles show that (hb, page_size, X) pool blocks lower and fit the
chip's VMEM.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, so with several test
workers only the one that runs this file may touch it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ModelConfig, QuantConfig
from repro.core.quantizer import KVQuantizer
from repro.kernels.qattn import ops as qattn_ops
from repro.kernels.qattn import qattn as qattn_k
from repro.serving import pages

GEOMETRIES = {
    # id: (head_dim, quant schedule, n_early, q heads, kv heads)
    "qwen3-hd64-iw8": (64, "early_boost", 4, 16, 8),
    "hd128-iw7": (128, "uniform", 0, 16, 8),
    "qwen3-0.6b": (128, "early_boost", 4, 16, 8),
    "deepseek-7b": (128, "early_boost", 4, 32, 32),
}
SLOTS, NUM_PAGES, MAX_PAGES = 4, 64, 8
# (page size, geometry): the two small geometries at 16- and 128-token
# pages, the benchmark's models at their 128-token pages
PAGED = [(16, "qwen3-hd64-iw8"), (16, "hd128-iw7"), (128, "qwen3-hd64-iw8"),
         (128, "hd128-iw7"), (128, "qwen3-0.6b"), (128, "deepseek-7b")]
MULTI = [(16, "qwen3-hd64-iw8"), (16, "hd128-iw7"), (128, "qwen3-0.6b"),
         (128, "deepseek-7b")]


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        from jax.experimental import topologies
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure: no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def _geometry(name: str):
    head_dim, schedule, n_early, n_heads, n_kv = GEOMETRIES[name]
    cfg = ModelConfig(name=name, family="decoder", num_layers=28,
                      d_model=1024, num_heads=n_heads, num_kv_heads=n_kv,
                      d_ff=3072, vocab_size=1024, head_dim=head_dim)
    qz = KVQuantizer(QuantConfig(schedule=schedule, n_early=n_early).build(
        head_dim, cfg.num_layers))
    assert qz.config.resolved_storage == "bitpack"
    return cfg, qz


def _placed(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _layer_pool(cfg, qz, page_size: int, sharding):
    """ShapeDtypeStructs of one layer's (K, V) pool."""
    pool = jax.eval_shape(lambda: pages.init_paged_cache(
        cfg, qz, NUM_PAGES, page_size, SLOTS, MAX_PAGES))
    layer = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), (pool.k, pool.v))
    return _placed(layer, sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("page_size, geometry", PAGED,
                         ids=[f"{ps}-{g}" for ps, g in PAGED])
def test_paged_qattn_compiles_for_v5e(one_chip, geometry, page_size):
    cfg, qz = _geometry(geometry)
    nk, nv = (int(x[0]) for x in qz.config.schedule.as_arrays())
    q = jax.ShapeDtypeStruct((SLOTS, 1, cfg.num_heads, cfg.head_dim),
                             jnp.float32, sharding=one_chip)
    table = jax.ShapeDtypeStruct((SLOTS, MAX_PAGES), jnp.int32,
                                 sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)
    text = _compiled_text(
        lambda q, kv, t, n: qattn_ops.paged_attend_quant_cache_op(
            q, *kv, nk, nv, t, n, cfg, qz, interpret=False),
        q, _layer_pool(cfg, qz, page_size, one_chip), table, lengths)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("page_size, geometry", MULTI,
                         ids=[g for _, g in MULTI])
def test_paged_qattn_multi_compiles_for_v5e(one_chip, geometry, page_size):
    cfg, qz = _geometry(geometry)
    nk, nv = (int(x[0]) for x in qz.config.schedule.as_arrays())
    q_len = 5
    q = jax.ShapeDtypeStruct((SLOTS, q_len, cfg.num_heads, cfg.head_dim),
                             jnp.float32, sharding=one_chip)
    table = jax.ShapeDtypeStruct((SLOTS, MAX_PAGES), jnp.int32,
                                 sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)
    text = _compiled_text(
        lambda q, kv, t, n: qattn_ops.paged_attend_multi_quant_cache_op(
            q, *kv, nk, nv, t, n, cfg, qz, interpret=False),
        q, _layer_pool(cfg, qz, page_size, one_chip), table, lengths)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_contiguous_qattn_compiles_for_v5e(one_chip, geometry):
    cfg, qz = _geometry(geometry)
    nk, nv = (int(x[0]) for x in qz.config.schedule.as_arrays())
    t = 256
    enc = jax.eval_shape(lambda x: (qz.encode(x, nk, qz.config.k_norm),
                                    qz.encode(x, nv, qz.config.v_norm)),
                         jax.ShapeDtypeStruct(
                             (SLOTS, t, cfg.num_kv_heads, cfg.head_dim),
                             jnp.float32))
    q = jax.ShapeDtypeStruct((SLOTS, 1, cfg.num_heads, cfg.head_dim),
                             jnp.float32, sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)
    text = _compiled_text(
        lambda q, kv, n: qattn_ops.attend_quant_cache_op(
            q, *kv, nk, nv, n, cfg, qz, interpret=False, block_t=128),
        q, _placed(enc, one_chip), lengths)
    assert "tpu_custom_call" in text


def test_kernel_entry_points_take_no_default_interpret():
    """interpret is chosen by the caller of every kernel entry point; none
    of them guesses it from the platform."""
    import inspect

    for fn in (qattn_k.qattn, qattn_k.paged_qattn, qattn_k.paged_qattn_multi,
               qattn_ops.attend_quant_cache_op,
               qattn_ops.paged_attend_quant_cache_op,
               qattn_ops.paged_attend_multi_quant_cache_op):
        param = inspect.signature(inspect.unwrap(fn)).parameters["interpret"]
        assert param.default is inspect.Parameter.empty, fn
