"""Pluggable attention backends: the single dispatch point for the decode
hot loop.

A backend owns one cache representation and the four operations serving
needs from it:

    init_cache(batch, seq_len)                  fresh layer-stacked cache
    cache_from_prefill(kv_stack, lengths, ...)  wrap prefill scan outputs
    append(layer_cache, k, v, nk, nv, lengths)  write one token per sequence
    attend(q, layer_cache, nk, nv, n_valid)     masked attention over cache
    physical_bytes(cache)                       payload bytes (compression)
    attend_stream_bytes(cache)                  bytes attend reads per step

The quantized backends additionally serve the paged pool
(serving/pages.py; driven by the continuous-batching scheduler):

    init_paged_cache(num_pages, page_size, batch, max_pages)
    paged_append(layer_cache, k, v, nk, nv, page_table, lengths, active)
    paged_attend(q, layer_cache, nk, nv, page_table, lengths)

and the speculative-verify pair (q_len > 1, per-row causal offsets —
serving/speculate.py drives these through the scheduler):

    paged_append_multi(layer_cache, k, v, nk, nv, page_table, lengths, valid)
    paged_attend_multi(q, layer_cache, nk, nv, page_table, lengths)

quant-pallas resolves the page-table indirection inside the kernel
(scalar-prefetched table feeding the BlockSpec index_map); quant-xla
materializes the gather and runs the dense attend — its bitwise equality
with a contiguous cache makes it the parity oracle for the kernel path.

Three implementations:

    raw          bf16 cache, exact attention (reference / baseline)
    quant-xla    TurboAngle cache, pure-XLA Hadamard-domain attention —
                 dequantized K/V materialize in HBM (portable fallback)
    quant-pallas TurboAngle cache, fused Pallas flash-decode kernel —
                 dequantizes in VMEM (including unpacking the bit-packed
                 word stream), never materializes y-domain K/V; this is
                 the path that actually banks the compression bandwidth
                 win

Selection: `RunConfig.backend` ("auto" | "raw" | "quant-xla" |
"quant-pallas"). "auto" resolves from the run's quant settings and
`ModelConfig.use_pallas`. Backends are frozen dataclasses so they hash/eq
cleanly as jit closure constants.

All lengths are per-sequence (B,) vectors; scalars broadcast, so uniform
batches need no special casing.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.cache import kvcache
from repro.configs.base import ModelConfig, RunConfig
from repro.core.quantizer import KVQuantizer
from repro.kernels.qattn import ops as qattn_ops
from repro.kernels.qattn import qattn as qattn_kernels
from repro.serving import pages as pages_lib

BACKEND_NAMES = ("raw", "quant-xla", "quant-pallas")


def _clamp_pad(cfg: ModelConfig, pad_to):
    """Sliding-window caches never need more than `window` ring slots."""
    if pad_to is not None and cfg.sliding_window is not None:
        return min(pad_to, cfg.sliding_window)
    return pad_to


@runtime_checkable
class AttentionBackend(Protocol):
    """What `serving.decode` / `serving.engine` require of a backend.

    A backend owns one cache representation end to end; the engine never
    inspects cache internals, only threads the opaque value between these
    methods. All methods must be jit-traceable (they run inside the decode
    while_loop) except the two byte-accounting queries, which run host-side
    for reporting.
    """

    name: str
    cfg: ModelConfig
    quantizer: Optional[KVQuantizer]

    def init_cache(self, batch: int, seq_len: int):
        """Fresh zero-length layer-stacked cache for `batch` sequences of
        up to `seq_len` cached tokens each."""
        ...

    def cache_from_prefill(self, kv_stack, lengths, pad_to=None):
        """Wrap the prefill scan's layer-stacked K/V (already quantized
        for quant backends) into this backend's cache, right-padded to
        `pad_to` tokens; `lengths` is the (B,) valid-token vector."""
        ...

    def append(self, layer_cache, new_k, new_v, nk, nv, lengths):
        """Write one new token's K/V per sequence at each row's own slot
        `lengths[i]` (ring slot for windowed configs); `nk`/`nv` are the
        layer's codebook sizes (ignored by the raw backend). Returns the
        updated layer cache."""
        ...

    def attend(self, q, layer_cache, nk, nv, n_valid):
        """Masked attention of (B, 1, n_heads, head_dim) queries over the
        first `n_valid[i]` cached tokens of each row. Returns
        (B, 1, n_heads, head_dim) outputs in f32."""
        ...

    def physical_bytes(self, cache) -> int:
        """Stored payload bytes (what compression ratios are measured on;
        bookkeeping arrays excluded)."""
        ...

    def attend_stream_bytes(self, cache) -> int:
        """Bytes the attend path actually reads from HBM per decode step —
        the decode-bandwidth number (`benchmarks/decode_bandwidth.py`);
        differs from `physical_bytes` when a path widens or
        re-materializes data."""
        ...


@dataclasses.dataclass(frozen=True)
class RawBackend:
    """bf16/fp32 cache — the exactness baseline."""

    cfg: ModelConfig
    dtype: jnp.dtype = jnp.bfloat16
    name: str = "raw"
    quantizer: Optional[KVQuantizer] = None

    def init_cache(self, batch: int, seq_len: int):
        return kvcache.init_raw_cache(self.cfg, batch, seq_len, self.dtype)

    def cache_from_prefill(self, kv_stack, lengths, pad_to=None):
        # prefill emits K/V in compute dtype (often f32); store at the
        # cache dtype so the footprint matches what init_cache allocates
        kv_stack = jax.tree.map(lambda a: a.astype(self.dtype), kv_stack)
        return kvcache.cache_from_prefill(kv_stack, lengths, False,
                                          pad_to=_clamp_pad(self.cfg, pad_to),
                                          window=self.cfg.sliding_window)

    def append(self, layer_cache, new_k, new_v, nk, nv, lengths):
        layer_k, layer_v = layer_cache
        return kvcache.append_raw(layer_k, layer_v, new_k, new_v, lengths,
                                  self.cfg.sliding_window)

    def attend(self, q, layer_cache, nk, nv, n_valid):
        layer_k, layer_v = layer_cache
        return kvcache.attend_raw_cache(q, layer_k, layer_v, n_valid,
                                        self.cfg)

    def physical_bytes(self, cache) -> int:
        return kvcache.cache_physical_bytes(cache)

    def attend_stream_bytes(self, cache) -> int:
        """Cache bytes the attend path streams per decode step (= payload:
        the raw K/V arrays are read as stored)."""
        return kvcache.cache_physical_bytes(cache)


@dataclasses.dataclass(frozen=True)
class _QuantBackendBase:
    cfg: ModelConfig
    quantizer: KVQuantizer = None  # required; default only for field order

    def __post_init__(self):
        if self.quantizer is None:
            raise ValueError(f"{self.name} backend requires a KVQuantizer")

    def init_cache(self, batch: int, seq_len: int):
        return kvcache.init_quant_cache(self.cfg, self.quantizer, batch,
                                        seq_len)

    def cache_from_prefill(self, kv_stack, lengths, pad_to=None):
        return kvcache.cache_from_prefill(kv_stack, lengths, True,
                                          pad_to=_clamp_pad(self.cfg, pad_to),
                                          window=self.cfg.sliding_window)

    def append(self, layer_cache, new_k, new_v, nk, nv, lengths):
        layer_kq, layer_vq = layer_cache
        qz = self.quantizer
        new_kq = qz.encode(new_k, nk, qz.config.k_norm)
        new_vq = qz.encode(new_v, nv, qz.config.v_norm)
        window = self.cfg.sliding_window
        return (
            kvcache.append_quant(layer_kq, new_kq, lengths, window),
            kvcache.append_quant(layer_vq, new_vq, lengths, window),
        )

    def physical_bytes(self, cache) -> int:
        return kvcache.cache_physical_bytes(cache)

    def attend_stream_bytes(self, cache) -> int:
        """Cache bytes the attend path streams per decode step.

        For quant-xla this is the stored payload (indices + norm codes +
        per-vector min/max); the path additionally materializes the
        dequantized y-domain K/V in HBM at y_dtype — that extra traffic is
        the reason the Pallas path exists and is reported separately by
        `benchmarks/decode_bandwidth.py`.
        """
        return kvcache.cache_physical_bytes(cache)

    # ---- paged pool (serving/pages.py layout) --------------------------
    def init_paged_cache(self, num_pages: int, page_size: int, batch: int,
                         max_pages: int) -> pages_lib.PagedKVCache:
        return pages_lib.init_paged_cache(
            self.cfg, self.quantizer, num_pages, page_size, batch, max_pages)

    def paged_append(self, layer_cache, new_k, new_v, nk, nv, page_table,
                     lengths, active):
        """Encode one token per slot and scatter it through the page table.

        layer_cache is one layer's (K, V) pool slice — arrays
        (P, n_kv, page_size, ...). Inactive slots write the reserved trash
        page (see serving/pages.py)."""
        layer_kq, layer_vq = layer_cache
        qz = self.quantizer
        ps = layer_kq.indices.shape[2]
        new_kq = qz.encode(new_k, nk, qz.config.k_norm)
        new_vq = qz.encode(new_v, nv, qz.config.v_norm)
        return (
            pages_lib.append_token_pages(layer_kq, new_kq, page_table,
                                         lengths, active, ps),
            pages_lib.append_token_pages(layer_vq, new_vq, page_table,
                                         lengths, active, ps),
        )

    def paged_attend(self, q, layer_cache, nk, nv, page_table, lengths):
        """XLA fallback indirection: materialize the contiguous
        (B, max_pages*ps, ...) gather, then run the dense quant attend.
        Bitwise-identical to a contiguous cache of the same width (parity
        oracle for the kernel path)."""
        layer_kq, layer_vq = layer_cache
        dense_k = pages_lib.gather_pages(layer_kq, page_table)
        dense_v = pages_lib.gather_pages(layer_vq, page_table)
        y_dtype = getattr(self, "y_dtype", jnp.float32)
        return kvcache.attend_quant_cache(
            q, dense_k, dense_v, nk, nv, lengths, self.cfg, self.quantizer,
            y_dtype=y_dtype)

    # ---- speculative verify (q_len > 1, per-row causal offsets) --------
    def paged_append_multi(self, layer_cache, new_k, new_v, nk, nv,
                           page_table, lengths, valid):
        """Optimistically append up to q_len tokens per slot in one
        scatter (the draft-verify path's transactional write). new_k/v are
        (B, q_len, n_kv, h); `valid` is the (B, q_len) write mask —
        padding rows and non-owned / inactive slots are redirected to the
        trash page. Rejected tokens are rolled back by bookkeeping alone
        (`pages.pop_tokens`): their codes stay as dead bytes past the
        frontier, masked by every attend path."""
        layer_kq, layer_vq = layer_cache
        qz = self.quantizer
        ps = layer_kq.indices.shape[2]
        new_kq = qz.encode(new_k, nk, qz.config.k_norm)
        new_vq = qz.encode(new_v, nv, qz.config.v_norm)
        return (
            pages_lib.append_tokens_pages(layer_kq, new_kq, page_table,
                                          lengths, valid, ps),
            pages_lib.append_tokens_pages(layer_vq, new_vq, page_table,
                                          lengths, valid, ps),
        )

    def paged_attend_multi(self, q, layer_cache, nk, nv, page_table,
                           lengths):
        """Score q_len tokens per slot in ONE dispatch: query row j of
        slot i attends over the first `lengths[i] + j + 1` cached tokens
        (per-row causal offsets — see `kernels.qattn.qattn.verify_rows`).
        Implemented by expanding (slot, row) pairs into B*q_len
        independent rows through this backend's own `paged_attend` —
        the ONE verify implementation for both backends: the pallas
        subclass dispatches to its fused kernel, the XLA subclass to its
        gather oracle, so each row reproduces the plain decode step's
        accumulation bit-for-bit on either path.
        q: (B, q_len, nq, h) -> (B, q_len, nq, h) f32."""
        b, q_len, nq, h = q.shape
        rows_table, rows_len = qattn_kernels.verify_rows(
            page_table, lengths, q_len)
        out = self.paged_attend(q.reshape(b * q_len, 1, nq, h), layer_cache,
                                nk, nv, rows_table, rows_len)
        return out.reshape(b, q_len, nq, h)


@dataclasses.dataclass(frozen=True)
class QuantXLABackend(_QuantBackendBase):
    """TurboAngle cache, pure-XLA attention (y-domain K/V hit HBM).

    y_dtype: precision of the materialized dequantized K/V. bf16 halves the
    HBM traffic this fallback pays; float32 matches quant-pallas bit-for-bit
    (the kernel always dequantizes in f32 VMEM) and is what parity tests use.
    """

    name: str = "quant-xla"
    y_dtype: jnp.dtype = jnp.bfloat16

    def attend(self, q, layer_cache, nk, nv, n_valid):
        layer_kq, layer_vq = layer_cache
        return kvcache.attend_quant_cache(
            q, layer_kq, layer_vq, nk, nv, n_valid, self.cfg, self.quantizer,
            y_dtype=self.y_dtype)


@dataclasses.dataclass(frozen=True)
class QuantPallasBackend(_QuantBackendBase):
    """TurboAngle cache, fused Pallas flash-decode (in-VMEM dequant).

    Reads the cache in whatever representation the quantizer stores —
    bit-packed uint32 word streams (the default; unpacked in VMEM inside
    the kernel) or legacy uint8/uint16 containers.

    interpret is required and chosen by whoever builds the backend: True
    runs the kernel bodies in the Pallas interpreter (CPU tests), False
    compiles them for the TPU. A compiled backend on a platform without a
    TPU raises at construction — it never falls back to the interpreter.

    block_t overrides the kernel's VMEM-derived token-block size; setting
    it to a paged engine's page_size makes the contiguous kernel's
    accumulation order bit-for-bit the paged kernel's (parity tests and
    the serve-throughput baseline use this).
    """

    name: str = "quant-pallas"
    interpret: Optional[bool] = None  # required; default only for order
    block_t: Optional[int] = None

    def __post_init__(self):
        super().__post_init__()
        if self.interpret is None:
            raise ValueError(
                "quant-pallas needs interpret=True (Pallas interpreter) or "
                "interpret=False (compiled kernels, TPU only)")
        if not self.interpret and jax.default_backend() != "tpu":
            raise RuntimeError(
                f"compiled quant-pallas kernels need a TPU; the default "
                f"JAX backend is {jax.default_backend()!r} (pass "
                f"interpret=True to run them in the Pallas interpreter)")

    def attend(self, q, layer_cache, nk, nv, n_valid):
        layer_kq, layer_vq = layer_cache
        return qattn_ops.attend_quant_cache_op(
            q, layer_kq, layer_vq, nk, nv, n_valid, self.cfg,
            self.quantizer, interpret=self.interpret, block_t=self.block_t)

    def attend_stream_bytes(self, cache) -> int:
        """Cache bytes the kernel streams from HBM per decode step.

        Bit-packed storage feeds the uint32 word stream straight into the
        kernel, so this equals the stored payload. The legacy uint8
        container path widens angle codes to i32 before the pallas_call —
        the widened array is what actually crosses HBM, and that is what
        gets counted (it is the honest baseline the packed path beats).
        """
        stored = kvcache.cache_physical_bytes(cache)
        if self.quantizer.config.resolved_storage == "bitpack":
            return stored
        widen = 4 - cache.k.indices.dtype.itemsize
        return stored + widen * (cache.k.indices.size + cache.v.indices.size)

    def paged_attend(self, q, layer_cache, nk, nv, page_table, lengths):
        """Page-table indirection inside the kernel: each grid step's K/V
        block resolves through the scalar-prefetched page table, streaming
        only the pages each slot owns — no contiguous materialization."""
        layer_kq, layer_vq = layer_cache
        return qattn_ops.paged_attend_quant_cache_op(
            q, layer_kq, layer_vq, nk, nv, page_table, lengths, self.cfg,
            self.quantizer, interpret=self.interpret)

    def paged_attend_multi(self, q, layer_cache, nk, nv, page_table,
                           lengths):
        """Fused verify: all q_len query rows of a slot share ONE page
        walk (`paged_qattn_multi` — per-row causal frontiers applied as
        score masks inside the kernel), instead of the base class's
        `verify_rows` expansion that walks every page q_len times. The
        quant-xla base implementation stays the parity oracle: both
        produce bit-identical outputs (tests/test_speculate.py), this one
        at ~1/q_len the kernel work — the difference between speculation
        saving steps on paper and saving milliseconds."""
        layer_kq, layer_vq = layer_cache
        return qattn_ops.paged_attend_multi_quant_cache_op(
            q, layer_kq, layer_vq, nk, nv, page_table, lengths, self.cfg,
            self.quantizer, interpret=self.interpret)


def get_backend(
    name: str,
    cfg: ModelConfig,
    quantizer: Optional[KVQuantizer] = None,
    *,
    dtype=jnp.bfloat16,
    interpret: Optional[bool] = None,
) -> AttentionBackend:
    """Construct a backend by name. Quant backends require a quantizer;
    quant-pallas also requires `interpret` (see QuantPallasBackend)."""
    if name == "raw":
        return RawBackend(cfg, dtype=dtype)
    if name == "quant-xla":
        return QuantXLABackend(cfg, quantizer)
    if name == "quant-pallas":
        return QuantPallasBackend(cfg, quantizer, interpret=interpret)
    raise ValueError(f"unknown backend {name!r}; expected {BACKEND_NAMES}")


def default_backend(cfg: ModelConfig, quantizer: Optional[KVQuantizer],
                    *, interpret: Optional[bool] = None) -> AttentionBackend:
    """Legacy-compatible resolution from a bare (cfg, quantizer) pair."""
    if quantizer is None:
        return RawBackend(cfg)
    if cfg.use_pallas:
        return QuantPallasBackend(cfg, quantizer, interpret=interpret)
    return QuantXLABackend(cfg, quantizer)


def from_run(run: RunConfig, quantizer: Optional[KVQuantizer], *,
             interpret: Optional[bool] = None) -> AttentionBackend:
    """Resolve `RunConfig.backend` ("auto" defers to quant/use_pallas).
    `interpret` is passed to a quant-pallas backend, which requires it."""
    name = run.backend
    if name == "auto":
        return default_backend(run.model, quantizer, interpret=interpret)
    if name != "raw" and quantizer is None:
        raise ValueError(
            f"backend {name!r} needs quantization enabled (run.quant)")
    return get_backend(name, run.model, quantizer, interpret=interpret)
