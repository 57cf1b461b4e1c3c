"""jit'd wrapper: quantized-cache decode attention via the Pallas kernel.

Mirrors `repro.cache.kvcache.attend_quant_cache` (the pure-XLA path). Which
path serves the decode hot loop is decided by the attention-backend layer in
`repro.serving.backends`: the `quant-pallas` backend calls this wrapper, the
`quant-xla` backend calls the XLA path, and `repro.serving.decode` dispatches
through whichever backend it was handed. `ModelConfig.use_pallas` only sets
the *default* backend (`RunConfig.backend = "auto"` resolves to quant-pallas
when it is true); an explicit `RunConfig.backend` always wins.

`n_valid` may be per-sequence (B,) and `n_bins_k/v` may be traced per-layer
scan values — both are runtime inputs of the kernel, so a mixed (early-boost
/ selective) schedule runs through one compiled kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.quantizer import KVQuantizer, QuantizedKV
from repro.kernels.qattn import qattn as k


def attend_quant_cache_op(
    q: jax.Array,  # (B, 1, nq, h) RoPE'd query, logical head dim
    layer_kq: QuantizedKV,  # (B, T, n_kv, ...)
    layer_vq: QuantizedKV,
    n_bins_k,  # int or traced i32 scalar
    n_bins_v,
    n_valid: jax.Array,  # (B,) or () int32
    cfg: ModelConfig,
    qz: KVQuantizer,
    *,
    interpret: bool,
    block_t: int | None = None,
) -> jax.Array:
    b, _, nq, h = q.shape
    nkv, g = cfg.num_kv_heads, cfg.q_per_kv
    dp = qz.config.d_pad
    if cfg.sliding_window is not None:
        # mirror kvcache._score_mask: once a sequence decodes past the
        # window, only `window` ring slots are live — without this clamp the
        # kernel's row_ok (= row < n_valid) would admit never-written slots
        n_valid = jnp.minimum(jnp.asarray(n_valid, jnp.int32),
                              cfg.sliding_window)
    scale = 1.0 / np.sqrt(h)
    q_rot = (qz.rotate_query(q[:, 0]) * scale).reshape(b, nkv, g, dp)
    kc, vc = qz.config.k_norm, qz.config.v_norm
    if qz.config.resolved_storage == "bitpack":
        # the kernel unpacks the uint32 word stream in VMEM — the packed
        # payload is exactly what crosses HBM
        k_idx, v_idx = layer_kq.indices, layer_vq.indices
        idx_bits = qz.config.index_width
    else:
        # legacy container path: codes are widened to i32 before the kernel
        # (the HBM stream the kernel reads is the widened array — measured
        # by benchmarks/decode_bandwidth.py as the uint8-storage baseline)
        k_idx = layer_kq.indices.astype(jnp.int32)
        v_idx = layer_vq.indices.astype(jnp.int32)
        idx_bits = None
    out_y = k.qattn(
        q_rot,
        k_idx, layer_kq.norm_codes,
        layer_kq.rmin, layer_kq.rmax,
        v_idx, layer_vq.norm_codes,
        layer_vq.rmin, layer_vq.rmax,
        n_valid,
        n_bins_k=n_bins_k, n_bins_v=n_bins_v,
        idx_bits=idx_bits,
        k_bits=kc.bits, k_log=kc.log_space,
        k_nq_packed=qz.config.norm_packed(kc),
        v_bits=vc.bits, v_log=vc.log_space,
        v_nq_packed=qz.config.norm_packed(vc),
        block_t=block_t,
        interpret=interpret,
    )
    out = qz.unrotate_output(out_y)  # one inverse transform per query
    return out.reshape(b, 1, nq, h)


def paged_attend_quant_cache_op(
    q: jax.Array,  # (B, 1, nq, h) RoPE'd query, logical head dim
    layer_kq: QuantizedKV,  # (P, n_kv, page_size, ...) one layer's pool
    layer_vq: QuantizedKV,
    n_bins_k,  # int or traced i32 scalar
    n_bins_v,
    page_table: jax.Array,  # (B, max_pages) int32
    lengths: jax.Array,  # (B,) int32
    cfg: ModelConfig,
    qz: KVQuantizer,
    *,
    interpret: bool,
) -> jax.Array:
    """Paged mirror of `attend_quant_cache_op`: the kernel resolves each
    grid step's K/V block through the scalar-prefetched page table instead
    of assuming contiguous ring layout. Sliding windows are a contiguous-
    cache concept (ring slots); the paged pool rejects them at init."""
    b, _, nq, h = q.shape
    nkv, g = cfg.num_kv_heads, cfg.q_per_kv
    dp = qz.config.d_pad
    scale = 1.0 / np.sqrt(h)
    q_rot = (qz.rotate_query(q[:, 0]) * scale).reshape(b, nkv, g, dp)
    kc, vc = qz.config.k_norm, qz.config.v_norm
    if qz.config.resolved_storage == "bitpack":
        k_idx, v_idx = layer_kq.indices, layer_vq.indices
        idx_bits = qz.config.index_width
    else:
        k_idx = layer_kq.indices.astype(jnp.int32)
        v_idx = layer_vq.indices.astype(jnp.int32)
        idx_bits = None
    out_y = k.paged_qattn(
        q_rot,
        k_idx, layer_kq.norm_codes,
        layer_kq.rmin, layer_kq.rmax,
        v_idx, layer_vq.norm_codes,
        layer_vq.rmin, layer_vq.rmax,
        page_table, lengths,
        n_bins_k=n_bins_k, n_bins_v=n_bins_v,
        idx_bits=idx_bits,
        k_bits=kc.bits, k_log=kc.log_space,
        k_nq_packed=qz.config.norm_packed(kc),
        v_bits=vc.bits, v_log=vc.log_space,
        v_nq_packed=qz.config.norm_packed(vc),
        interpret=interpret,
    )
    out = qz.unrotate_output(out_y)
    return out.reshape(b, 1, nq, h)


def paged_attend_multi_quant_cache_op(
    q: jax.Array,  # (B, q_len, nq, h) RoPE'd queries, logical head dim
    layer_kq: QuantizedKV,  # (P, n_kv, page_size, ...) one layer's pool
    layer_vq: QuantizedKV,
    n_bins_k,
    n_bins_v,
    page_table: jax.Array,  # (B, max_pages) int32
    lengths: jax.Array,  # (B,) int32 committed tokens per slot
    cfg: ModelConfig,
    qz: KVQuantizer,
    *,
    interpret: bool,
) -> jax.Array:
    """Fused speculative verify: q_len query rows per slot share ONE page
    walk (`qattn.paged_qattn_multi`); query row j applies its own causal
    frontier lengths[i] + j + 1 as a score mask. Bit-for-bit the
    `verify_rows` expansion (which the quant-xla backend keeps as the
    parity oracle), at ~1/q_len of its page-walk cost — the kernel-side
    half of making speculation's step savings show up on the clock."""
    b, q_len, nq, h = q.shape
    nkv, g = cfg.num_kv_heads, cfg.q_per_kv
    dp = qz.config.d_pad
    scale = 1.0 / np.sqrt(h)
    # rotate all rows at once, then order rows j-major so row r = j*g + gi
    # matches the kernel's frontier derivation (j = r // g)
    q_rot = (qz.rotate_query(q.reshape(b * q_len, nq, h)) * scale
             ).reshape(b, q_len, nkv, g, dp)
    q_rot = q_rot.transpose(0, 2, 1, 3, 4).reshape(b, nkv, q_len * g, dp)
    kc, vc = qz.config.k_norm, qz.config.v_norm
    if qz.config.resolved_storage == "bitpack":
        k_idx, v_idx = layer_kq.indices, layer_vq.indices
        idx_bits = qz.config.index_width
    else:
        k_idx = layer_kq.indices.astype(jnp.int32)
        v_idx = layer_vq.indices.astype(jnp.int32)
        idx_bits = None
    out_y = k.paged_qattn_multi(
        q_rot,
        k_idx, layer_kq.norm_codes,
        layer_kq.rmin, layer_kq.rmax,
        v_idx, layer_vq.norm_codes,
        layer_vq.rmin, layer_vq.rmax,
        page_table, lengths,
        q_len=q_len, g=g,
        n_bins_k=n_bins_k, n_bins_v=n_bins_v,
        idx_bits=idx_bits,
        k_bits=kc.bits, k_log=kc.log_space,
        k_nq_packed=qz.config.norm_packed(kc),
        v_bits=vc.bits, v_log=vc.log_space,
        v_nq_packed=qz.config.norm_packed(vc),
        interpret=interpret,
    )
    out_y = out_y.reshape(b, nkv, q_len, g, dp).transpose(0, 2, 1, 3, 4)
    out = qz.unrotate_output(out_y.reshape(b * q_len, nkv, g, dp))
    return out.reshape(b, q_len, nq, h)
