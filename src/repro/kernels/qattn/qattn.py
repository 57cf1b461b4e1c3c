"""Pallas TPU kernel: flash-decode attention over a TurboAngle-quantized
KV cache, fused with in-VMEM dequantization (Hadamard domain).

Why this is the perf-critical kernel: long-context decode is bound by
reading the KV cache once per token. Storing angles+norms at ~6.6 bits/elem
cuts those HBM bytes ~2.4x vs bf16 — but only if the dequant happens INSIDE
the attention kernel; a separate dequant pass would write the f32 cache back
to HBM and forfeit the entire win (exactly what the pure-XLA path does).

Bit-packed streams: the cache's default representation is a little-endian
uint32 word stream (~3.5 angle bits/elem at K128) plus two-per-byte norm
nibbles. The kernel reads those words directly and unpacks them in VMEM
with static row slices and shifts (`packing.unpack_bits_T`), so the HBM
stream per step is the packed payload itself — the paper's bit budget is
what physically moves. The legacy "uint8" container path is kept for
comparison benchmarks (`idx_bits=None`).

Beyond-paper fusion: scores are taken directly against Hadamard-domain keys
(q.k == (HDq).(HDk)) and the weighted value sum is accumulated in the
Hadamard domain — the inverse FWHT runs ONCE per query on the output instead
of once per cached token (O(T d log d) -> O(d log d) reconstruction FLOPs).

Layout note: inside the kernel, y-vectors live in split-half ("[even|odd]")
order — pair p contributes rows p and p+pairs instead of 2p and 2p+1, and
tokens run along the minor (lane) axis. Dot products are
permutation-invariant, so the wrapper permutes the (tiny) query once per
call and un-permutes the (tiny) output once per call; the hot loop then
builds each (d_pad, page_size) tile with one concatenate.

Pool layout: every pool leaf is (P, n_kv, page_size, X). A grid step's
block is one page of `hb` kv heads, an (hb, page_size, X) tile whose two
minor dims are whole array dims — the form the TPU lowering accepts for
any X (index words, norm codes, range scalars). Grid: (B, n_kv / hb,
max_pages), accumulating online-softmax state in VMEM scratch across the
sequential page dimension; the page id of each step comes from the
scalar-prefetched page table in the BlockSpec index_map. Inside a step
a static loop runs the one-head arithmetic unchanged on each of the hb
heads, so the output does not depend on hb. `hb` comes from the pool's
shapes (`default_head_block`). Dequantization sets the kernel's pace; with
one head a step the next step's DMAs outlast the head in hand and part of
their time shows, with a few heads they finish under the arithmetic.

Serving integration: `lengths` is a per-sequence (B,) vector (ragged
batches) and the codebook sizes `n_bins_k`/`n_bins_v` are *runtime*
scalars prefetched into SMEM — they ride along the per-layer MixedKV scan
as traced values, so one compiled kernel serves every layer of a mixed
schedule. Only the storage geometry (index bits, norm format) is
compile-time static.

`interpret` has no default: callers choose the Pallas interpreter (CPU
tests) or the compiled kernel (TPU) explicitly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import packing

TWO_PI = 2.0 * np.pi
NEG_INF = -1e30

# VMEM spent on one grid step's cache tiles (dequant f32 tiles + code
# streams), out of ~16 MiB/core; the rest is left for q/output blocks,
# softmax scratch and the pipeline's double buffering.
DEFAULT_VMEM_BUDGET = 4 * 1024 * 1024

# VMEM for one paged grid step's pool tiles, double-buffered and counted
# as laid out in VMEM (`_vmem_tile_bytes`). The kernel's dequantization,
# not its DMA, sets its pace: a block of a few heads lets the next block's
# DMA run under this block's arithmetic, while each further head only
# lengthens the unrolled body. On a TPU v5e at 128-token pages and
# head_dim 128 (416 KiB a head-page) this budget's 2 heads a step ran
# fastest; 4 and 8 heads ran 5 % and 10 % slower.
PAGED_VMEM_BUDGET = 2 * 1024 * 1024

# the kernel's dots run in full f32 on the MXU (several bf16 passes), the
# precision the dequantized tiles are computed in; the CPU ignores it
_F32 = jax.lax.Precision.HIGHEST

_STATIC = ("idx_bits", "k_bits", "k_log", "k_nq_packed", "v_bits", "v_log",
           "v_nq_packed", "interpret")


def default_block_t(dp: int, row_stream_bytes: int,
                    vmem_budget: int = DEFAULT_VMEM_BUDGET) -> int:
    """Largest block_t whose per-step VMEM footprint fits the budget.

    Per cache row a step holds: two f32 dequant tiles (K and V, dp each)
    plus the packed/container code+norm streams (`row_stream_bytes`). The
    factor 2 reserves double-buffering headroom for the next block's DMA.
    Rounded down to a sublane-friendly multiple of 128, clamped to
    [128, 2048].
    """
    per_row = 2 * dp * 4 + row_stream_bytes
    bt = vmem_budget // (2 * per_row)
    return max(128, min(2048, (bt // 128) * 128))


def _vmem_tile_bytes(rows: int, cols: int, dtype) -> int:
    """Bytes a (rows, cols) tile takes in VMEM: the minor dim padded to
    128 lanes, the second-minor to the dtype's sublane tile (8 rows of
    32-bit, 16 of 16-bit, 32 of 8-bit)."""
    itemsize = np.dtype(dtype).itemsize
    sublanes = 8 * max(1, 4 // itemsize)
    padded_rows = -(-rows // sublanes) * sublanes
    padded_cols = -(-cols // 128) * 128
    return padded_rows * padded_cols * itemsize


def default_head_block(pool, vmem_budget: int = PAGED_VMEM_BUDGET) -> int:
    """kv heads per paged grid step: the largest divisor of n_kv whose
    double-buffered pool tiles fit the budget (1 if none does).

    `pool` is the 8 leaves (P, n_kv, page_size, X), arrays or shapes. The
    tiles are counted padded, as VMEM holds them: the range scalars'
    (page_size, 1) f32 tiles and the narrow index/norm streams take far
    more VMEM than HBM bytes, and the padded count is what has to fit.
    """
    nkv = pool[0].shape[1]
    per_head = sum(_vmem_tile_bytes(a.shape[2], a.shape[3], a.dtype)
                   for a in pool)
    return max((hb for hb in range(1, nkv + 1)
                if nkv % hb == 0 and 2 * hb * per_head <= vmem_budget),
               default=1)


def _dequant_block(idx_raw, nq_raw, rmin, rmax, *, n_bins, bits, log,
                   pairs, idx_bits, nq_packed):
    """Stored codes -> (2*pairs, bt) y-domain block, f32, TRANSPOSED
    split-half layout: row p is pair p's cos line, row p+pairs its sin
    line, tokens along the minor axis.

    idx_raw: (bt, words) uint32 bitstream (idx_bits static) or (bt, pairs)
    int32 container codes (idx_bits None). nq_raw: (bt, pairs//2) nibble
    bytes, (bt, pairs) uint8 codes, or (bt, pairs) f32 norms. n_bins may be
    a traced i32 scalar (read off the bins ref).
    """
    if idx_bits is None:
        idx = idx_raw.astype(jnp.int32).T  # (pairs, bt)
    else:
        idx = packing.unpack_bits_T(idx_raw, idx_bits, pairs)
    if bits is None:
        r = nq_raw.astype(jnp.float32).T
    else:
        # widen to i32 before f32: the TPU lowering has no u8 -> f32 cast
        nq = (packing.unpack_nibbles(nq_raw, pairs) if nq_packed
              else nq_raw.astype(jnp.int32))
        levels = float(2**bits - 1)
        scale = jnp.maximum(rmax - rmin, 1e-12)
        v = nq.astype(jnp.float32) / levels * scale + rmin
        r = (jnp.exp(v) if log else v).T  # (pairs, bt)
    # bin-center angle folded into one multiply-add:
    # (k + 0.5) * 2pi/n == k * s + 0.5 * s with s = 2pi/n
    ang = TWO_PI / jnp.asarray(n_bins, jnp.float32)
    theta = idx.astype(jnp.float32) * ang + 0.5 * ang
    return jnp.concatenate([r * jnp.cos(theta), r * jnp.sin(theta)], axis=0)


def _page_tiles(refs, h, bins_ref, col_ok, geom):
    """Dequantize head h's K and V pages of this grid step -> two (dp, ps)
    tiles, with columns past the frontier zeroed (unwritten pool bytes may
    decode to NaN/Inf, and 0 * NaN would poison the dots)."""
    kidx, knq, krmin, krmax, vidx, vnq, vrmin, vrmax = refs
    common = dict(pairs=geom["pairs"], idx_bits=geom["idx_bits"])
    y_k = _dequant_block(
        kidx[h], knq[h], krmin[h], krmax[h], n_bins=bins_ref[0],
        bits=geom["k_bits"], log=geom["k_log"],
        nq_packed=geom["k_nq_packed"], **common)
    y_v = _dequant_block(
        vidx[h], vnq[h], vrmin[h], vrmax[h], n_bins=bins_ref[1],
        bits=geom["v_bits"], log=geom["v_log"],
        nq_packed=geom["v_nq_packed"], **common)
    return jnp.where(col_ok, y_k, 0.0), jnp.where(col_ok, y_v, 0.0)


def paged_qattn_kernel(pt_ref, len_ref, bins_ref, q_ref, *refs,
                       page_size: int, geom: dict):
    """One (slot, kv-head block, page) grid step of flash-decode.

    The K/V block for grid step p is whatever physical page `pt[b, p]`
    names — the gather happens in the BlockSpec index_map, so the DMA
    engine streams exactly the pages the slot owns. The block holds hb kv
    heads; each runs the one-head update below on its own slice of the
    q block, the pool tiles and the scratch.

    Per-page work bound: a page past this slot's frontier contributes
    exactly nothing (masked scores are NEG_INF -> p == 0, m unchanged), so
    its dequant + dots are skipped — each slot costs its own live page
    count, which is what lets short requests ride alongside a long-context
    slot without paying its width. Bit-for-bit identical to computing the
    masked page.
    """
    pool_refs, (o_ref, m_scr, l_scr, acc_scr) = refs[:8], refs[8:]
    b_i = pl.program_id(0)
    p_step = pl.program_id(2)

    @pl.when(p_step == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b_i]

    @pl.when(p_step * page_size < length)
    def _work():
        col_pos = p_step * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        col_ok = col_pos < length

        for h in range(q_ref.shape[0]):  # the block's kv heads, unrolled
            q = q_ref[h]  # (g, dp) pre-rotated, pre-scaled, split-half
            y_k, y_v = _page_tiles(pool_refs, h, bins_ref, col_ok, geom)
            s = jax.lax.dot_general(
                q.astype(jnp.float32), y_k, (((1,), (0,)), ((), ())),
                precision=_F32)  # (g, ps)
            s = jnp.where(col_ok, s, NEG_INF)

            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            m_scr[h] = m_new
            pv = jax.lax.dot_general(p, y_v, (((1,), (1,)), ((), ())),
                                     precision=_F32)  # (g, dp)
            acc_scr[h] = acc_scr[h] * corr + pv

    @pl.when(p_step == pl.num_programs(2) - 1)
    def _fin():
        o_ref[...] = (acc_scr[...]
                      / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_qattn_multi_kernel(pt_ref, len_ref, bins_ref, q_ref, *refs,
                             page_size: int, q_len: int, g: int, geom: dict):
    """Speculative-verify attention: all q_len query rows of a slot share
    ONE walk over its pages.

    The q block carries all q_len*g query rows of each kv head in the
    step's block, and each head's page is dequantized ONCE; row r (query
    position j = r // g)
    applies its own causal frontier

        lengths[slot] + j + 1

    as a score mask. Masked scores are NEG_INF, so their softmax weight is
    exactly zero and each row's m/l/acc sequence is term-for-term the
    single-query kernel's at its own frontier — the fused walk is
    bit-for-bit the `verify_rows` expansion (pinned by
    tests/test_speculate.py).

    The dots stay (g, ·)-shaped — a static per-position loop over the
    shared dequantized tiles — rather than one (q_len*g, ·) GEMM: a gemm's
    k-dimension accumulation order can change with the output row count,
    which would break the bitwise-parity contract. The frontier masking
    and the running-max update ARE batched across all q_len*g rows (max
    and compare are exact, row-count-independent ops); exp and the scaled
    l/acc updates stay per-row-group because XLA's codegen for them is
    shape-dependent at the ulp level.
    """
    pool_refs, (o_ref, m_scr, l_scr, acc_scr) = refs[:8], refs[8:]
    b_i = pl.program_id(0)
    p_step = pl.program_id(2)

    @pl.when(p_step == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b_i]

    # the furthest frontier is length + q_len: pages wholly past it
    # contribute nothing to any row
    @pl.when(p_step * page_size < length + q_len)
    def _work():
        col_pos = p_step * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        # query position j's causal frontier: the committed tokens plus
        # the j+1 this dispatch appended (its own key included)
        row_j = jax.lax.broadcasted_iota(
            jnp.int32, (q_len * g, 1), 0) // g

        for h in range(q_ref.shape[0]):  # the block's kv heads, unrolled
            q = q_ref[h]  # (q_len*g, dp)
            y_k, y_v = _page_tiles(pool_refs, h, bins_ref,
                                   col_pos < length + q_len, geom)
            s = jnp.concatenate(
                [jax.lax.dot_general(
                    q[j * g:(j + 1) * g].astype(jnp.float32), y_k,
                    (((1,), (0,)), ((), ())), precision=_F32)  # (g, ps)
                 for j in range(q_len)], axis=0)  # (q_len*g, ps)
            s = jnp.where(col_pos < length + 1 + row_j, s, NEG_INF)

            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            m_scr[h] = m_new
            l_prev = l_scr[h]
            acc_prev = acc_scr[h]
            l_new, acc_new = [], []
            for j in range(q_len):
                rows = slice(j * g, (j + 1) * g)
                p = jnp.exp(s[rows] - m_new[rows])
                corr = jnp.exp(m_prev[rows] - m_new[rows])
                l_new.append(l_prev[rows] * corr
                             + jnp.sum(p, axis=-1, keepdims=True))
                pv = jax.lax.dot_general(p, y_v, (((1,), (1,)), ((), ())),
                                         precision=_F32)
                acc_new.append(acc_prev[rows] * corr + pv)
            l_scr[h] = jnp.concatenate(l_new, axis=0)
            acc_scr[h] = jnp.concatenate(acc_new, axis=0)

    @pl.when(p_step == pl.num_programs(2) - 1)
    def _fin():
        o_ref[...] = (acc_scr[...]
                      / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def _to_split_half(x: jax.Array) -> jax.Array:
    """(..., dp) interleaved (even0, odd0, even1, ...) -> [evens | odds]."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def _from_split_half(x: jax.Array) -> jax.Array:
    """Inverse of _to_split_half."""
    dp = x.shape[-1]
    pairs = dp // 2
    return jnp.stack([x[..., :pairs], x[..., pairs:]],
                     axis=-1).reshape(*x.shape[:-1], dp)


def _paged_call(kernel, q_rot, pool, page_table, lengths, n_bins_k,
                n_bins_v, *, interpret: bool):
    """Run a paged kernel body over the pool: grid (B, n_kv / hb,
    max_pages), page table / lengths / bins scalar-prefetched into SMEM,
    one (hb, page_size, X) tile of every pool leaf per step, with hb =
    `default_head_block(pool)`. A page-table entry past a slot's frontier
    names the trash page, so consecutive dead steps name the same block
    and their DMA is skipped. q_rot is (B, n_kv, rows, dp) in interleaved
    order; returns the same shape, f32.
    """
    b, nkv, rows, dp = q_rot.shape
    page_size = pool[0].shape[2]
    mp = page_table.shape[1]
    hb = default_head_block(pool)
    bins = jnp.stack([
        jnp.asarray(n_bins_k, jnp.int32).reshape(()),
        jnp.asarray(n_bins_v, jnp.int32).reshape(()),
    ])
    sq = pl.squeezed

    def pool_spec(arr):
        return pl.BlockSpec(
            (sq, hb, page_size, arr.shape[-1]),
            lambda bi, hi, pi, pt, lens, bins_: (pt[bi, pi], hi, 0, 0))

    q_spec = pl.BlockSpec((sq, hb, rows, dp),
                          lambda bi, hi, pi, *_: (bi, hi, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # page_table, lengths, bins
        grid=(b, nkv // hb, mp),
        in_specs=[q_spec, *[pool_spec(a) for a in pool]],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((hb, rows, 1), jnp.float32),
            pltpu.VMEM((hb, rows, 1), jnp.float32),
            pltpu.VMEM((hb, rows, dp), jnp.float32),
        ],
    )
    out_perm = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nkv, rows, dp), jnp.float32),
        interpret=interpret,
        name="qattn_paged",
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32), bins,
      _to_split_half(q_rot), *pool)
    return _from_split_half(out_perm)


def _geom(q_rot, idx_bits, k_bits, k_log, k_nq_packed, v_bits, v_log,
          v_nq_packed) -> dict:
    return dict(pairs=q_rot.shape[-1] // 2, idx_bits=idx_bits,
                k_bits=k_bits, k_log=k_log, k_nq_packed=k_nq_packed,
                v_bits=v_bits, v_log=v_log, v_nq_packed=v_nq_packed)


@functools.partial(jax.jit, static_argnames=_STATIC)
def paged_qattn(
    q_rot: jax.Array,  # (B, nkv, G, Dp) f32, pre-scaled
    k_idx: jax.Array,  # (P, nkv, ps, words) uint32 — ONE layer's pool
    k_nq: jax.Array,
    k_rmin: jax.Array,  # (P, nkv, ps, 1)
    k_rmax: jax.Array,
    v_idx: jax.Array,
    v_nq: jax.Array,
    v_rmin: jax.Array,
    v_rmax: jax.Array,
    page_table: jax.Array,  # (B, max_pages) int32 physical page ids
    lengths: jax.Array,  # (B,) int32 valid tokens per slot
    *,
    n_bins_k,  # int or traced i32 scalar
    n_bins_v,
    idx_bits=None,  # static: packed index width; None -> container codes
    k_bits=None,
    k_log: bool = False,
    k_nq_packed: bool = False,
    v_bits=None,
    v_log: bool = False,
    v_nq_packed: bool = False,
    interpret: bool,
) -> jax.Array:
    """Flash-decode over the paged pool. The block size IS the page size —
    one grid step streams one physical page of a block of kv heads per
    slot (`_paged_call`)."""
    geom = _geom(q_rot, idx_bits, k_bits, k_log, k_nq_packed, v_bits, v_log,
                 v_nq_packed)
    kernel = functools.partial(paged_qattn_kernel,
                               page_size=k_idx.shape[2], geom=geom)
    pool = (k_idx, k_nq, k_rmin, k_rmax, v_idx, v_nq, v_rmin, v_rmax)
    return _paged_call(kernel, q_rot, pool, page_table, lengths, n_bins_k,
                       n_bins_v, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("q_len", "g") + _STATIC)
def paged_qattn_multi(
    q_rot: jax.Array,  # (B, nkv, q_len*g, Dp) f32, pre-scaled, row r = j*g+gi
    k_idx: jax.Array,  # (P, nkv, ps, words) uint32 — ONE layer's pool
    k_nq: jax.Array,
    k_rmin: jax.Array,
    k_rmax: jax.Array,
    v_idx: jax.Array,
    v_nq: jax.Array,
    v_rmin: jax.Array,
    v_rmax: jax.Array,
    page_table: jax.Array,  # (B, max_pages) int32 physical page ids
    lengths: jax.Array,  # (B,) int32 committed tokens per slot
    *,
    q_len: int,
    g: int,
    n_bins_k,
    n_bins_v,
    idx_bits=None,
    k_bits=None,
    k_log: bool = False,
    k_nq_packed: bool = False,
    v_bits=None,
    v_log: bool = False,
    v_nq_packed: bool = False,
    interpret: bool,
) -> jax.Array:
    """Fused speculative-verify flash-decode: q_len query rows per slot,
    one page walk. Returns (B, nkv, q_len*g, Dp) f32 (split-half undone)."""
    rows = q_rot.shape[2]
    if rows != q_len * g:
        raise ValueError(f"q_rot rows {rows} != q_len*g = {q_len * g}")
    geom = _geom(q_rot, idx_bits, k_bits, k_log, k_nq_packed, v_bits, v_log,
                 v_nq_packed)
    kernel = functools.partial(paged_qattn_multi_kernel,
                               page_size=k_idx.shape[2], q_len=q_len, g=g,
                               geom=geom)
    pool = (k_idx, k_nq, k_rmin, k_rmax, v_idx, v_nq, v_rmin, v_rmax)
    return _paged_call(kernel, q_rot, pool, page_table, lengths, n_bins_k,
                       n_bins_v, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_t",) + _STATIC)
def qattn(
    q_rot: jax.Array,  # (B, nkv, G, Dp) f32, pre-scaled
    k_idx: jax.Array,  # (B, T, nkv, words) uint32 or (B, T, nkv, pairs) int
    k_nq: jax.Array,
    k_rmin: jax.Array,  # (B, T, nkv, 1)
    k_rmax: jax.Array,
    v_idx: jax.Array,
    v_nq: jax.Array,
    v_rmin: jax.Array,
    v_rmax: jax.Array,
    length: jax.Array,  # (B,) per-sequence valid counts, or () broadcast
    *,
    n_bins_k,  # int or traced i32 scalar (per-layer MixedKV scan value)
    n_bins_v,
    idx_bits=None,
    k_bits=None,
    k_log: bool = False,
    k_nq_packed: bool = False,
    v_bits=None,
    v_log: bool = False,
    v_nq_packed: bool = False,
    block_t: int | None = None,
    interpret: bool,
) -> jax.Array:
    """Flash-decode over a contiguous (B, T, nkv, X) cache.

    The cache is viewed as a pool of `block_t`-token pages — (B*T/block_t,
    nkv, block_t, X), slot b owning pages b*nb .. b*nb+nb-1 in order — and
    handed to the paged kernel. The transpose into page layout is one XLA
    copy of the cache per call (the contiguous cache serves the static
    engine, not the paged serving path); in exchange there is one kernel,
    and the contiguous attend is by construction bit-for-bit the paged
    attend at page_size == block_t. `block_t` defaults to a VMEM-budget
    value (`default_block_t`), clamped to T.
    """
    b, t = k_idx.shape[:2]
    if block_t is None:
        stream = sum(
            a.shape[-1] * a.dtype.itemsize
            for a in (k_idx, k_nq, v_idx, v_nq)) + 4 * 4  # + rmin/rmax pairs
        block_t = default_block_t(q_rot.shape[-1], stream)
    block_t = min(block_t, t)
    nb = -(-t // block_t)

    def as_pages(a):  # (B, T, nkv, X) -> (B*nb, nkv, block_t, X)
        a = jnp.pad(a, ((0, 0), (0, nb * block_t - t), (0, 0), (0, 0)))
        a = a.reshape(b, nb, block_t, *a.shape[2:])
        return jnp.swapaxes(a, 2, 3).reshape(b * nb, a.shape[3], block_t,
                                             a.shape[-1])

    from repro.cache.kvcache import per_seq_lengths

    table = jnp.arange(b * nb, dtype=jnp.int32).reshape(b, nb)
    return paged_qattn(
        q_rot, *[as_pages(a) for a in (k_idx, k_nq, k_rmin, k_rmax, v_idx,
                                        v_nq, v_rmin, v_rmax)],
        table, per_seq_lengths(length, b), n_bins_k=n_bins_k,
        n_bins_v=n_bins_v, idx_bits=idx_bits, k_bits=k_bits, k_log=k_log,
        k_nq_packed=k_nq_packed, v_bits=v_bits, v_log=v_log,
        v_nq_packed=v_nq_packed, interpret=interpret)


# ======================================================= verify rows ========
def verify_rows(page_table: jax.Array, lengths: jax.Array, q_len: int
                ) -> tuple[jax.Array, jax.Array]:
    """Expand (slot, verify-row) pairs into independent kernel rows.

    The speculative verify step scores `q_len` tokens per slot in ONE
    `paged_qattn` dispatch by treating each (slot i, query row j) pair as
    its own batch row with the per-row causal frontier

        lengths[i] + j + 1

    — query j attends over the prompt, every previously committed token,
    and the j+1 tokens appended by this very dispatch (its own position
    included), exactly the key set the plain single-token decode step
    would see at that position. No new kernel body is needed: the paged
    kernel already takes per-row lengths and a per-row page table, its
    online-softmax walks pages in the same order at every length, and
    pages past a row's frontier contribute exactly nothing — so each
    expanded row accumulates BIT-FOR-BIT like a plain decode step at its
    own length. That accumulation identity is what makes greedy
    speculative decoding lossless rather than approximately so (pinned by
    tests/test_speculate.py through both quant backends).

    jit-variant discipline: `q_len` must be the *static* maximum
    (draft_len + 1, shorter drafts padded) so a verify dispatch compiles
    one trace per page-table width bucket — the existing pow-2 live-width
    bucketing — and never a fresh variant per acceptance count. The
    scheduler asserts this before dispatch.

    Returns (row page table (B*q_len, max_pages), row lengths (B*q_len,)).
    """
    b = page_table.shape[0]
    rows_table = jnp.repeat(page_table, q_len, axis=0)
    rows_len = (jnp.asarray(lengths, jnp.int32)[:, None] + 1
                + jnp.arange(q_len, dtype=jnp.int32)[None, :])
    return rows_table, rows_len.reshape(b * q_len)
