"""Single-token decode steps for every family, over a pluggable attention
backend.

The decode step is the serving hot loop: it reads the whole KV cache once per
token (memory-bound at long context — exactly what TurboAngle compresses) and
appends the new token's (possibly quantized) K/V in-place (buffer donation
keeps it allocation-free across steps).

All cache interaction goes through ONE dispatch point — an
`AttentionBackend` from `repro.serving.backends` (raw bf16, quant-xla, or
quant-pallas). Lengths are per-sequence (B,) vectors, so ragged batches
decode correctly: each row appends at its own slot and masks its own tail.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.quantizer import KVQuantizer
from repro.models import attention, common, ssm, transformer, xlstm
from repro.serving import backends as backends_lib
from repro.serving.backends import AttentionBackend


class DecodeState(NamedTuple):
    """Everything carried between decode steps."""

    cache: Any  # RawKVCache | QuantKVCache | None
    states: Any  # recurrent states (hybrid/xlstm) or None


class ShardInfo(NamedTuple):
    """KV-head sharding of the paged pool, threaded into the step functions.

    `axis` is the mesh axis name the pool's kv-head dim is split over;
    `size` its extent. Inside `shard_map` each device holds
    num_kv_heads/size contiguous kv-heads (and the matching contiguous
    GQA group of q-heads), so the per-shard attend is bit-identical to the
    corresponding head slice of the full computation; only the attention
    outputs are all-gathered (head order == device order with
    `tiled=True`), which preserves the FP accumulation order of the wo
    projection and everything downstream."""

    axis: str
    size: int


def _shard_backend(cfg: ModelConfig, backend: AttentionBackend,
                   shard: ShardInfo):
    """Backend viewing only this device's head slice of the pool.

    Returns (local backend, local q-heads, local kv-heads). Both backends
    are frozen dataclasses, so a config-swap copy is cheap and keeps the
    quantizer (head_dim-indexed, shard-invariant) intact."""
    nq = cfg.num_heads // shard.size
    nkv = cfg.num_kv_heads // shard.size
    lcfg = dataclasses.replace(cfg, num_heads=nq, num_kv_heads=nkv)
    return dataclasses.replace(backend, cfg=lcfg), nq, nkv


def _resolve_backend(cfg: ModelConfig, backend: Optional[AttentionBackend],
                     quantizer: Optional[KVQuantizer]) -> AttentionBackend:
    if backend is not None:
        return backend
    return backends_lib.default_backend(cfg, quantizer)


def _attn_decode(
    layer_attn_params,
    x: jax.Array,  # (B, 1, D) pre-normed input
    positions: jax.Array,  # (B, 1) absolute position of this token per row
    layer_cache: tuple,
    nk: jax.Array,
    nv: jax.Array,
    lengths: jax.Array,  # (B,) tokens already cached per sequence
    cfg: ModelConfig,
    backend: AttentionBackend,
):
    """Attention sublayer at decode time. Returns (out (B,1,D), new cache)."""
    b = x.shape[0]
    q, k, v = attention.project_qkv(layer_attn_params, x, positions, cfg)
    new_cache = backend.append(layer_cache, k, v, nk, nv, lengths)
    out = backend.attend(q, new_cache, nk, nv, lengths + 1)
    out = out.reshape(b, 1, cfg.num_heads * cfg.head_dim).astype(x.dtype)
    return jnp.einsum("bsk,kd->bsd", out, layer_attn_params["wo"]), new_cache


def decode_step(
    params,
    cfg: ModelConfig,
    state: DecodeState,
    tokens: jax.Array,  # (B, 1) int32
    *,
    quantizer: Optional[KVQuantizer] = None,
    backend: Optional[AttentionBackend] = None,
    param_constraint=None,
    constraint=None,
) -> tuple[jax.Array, DecodeState]:
    """One decode step -> (logits (B, V), new DecodeState).

    `backend` is the attention-backend dispatch point; when omitted it is
    derived from (cfg.use_pallas, quantizer) for backward compatibility.
    """
    x = transformer.embed_inputs(params, cfg, {"tokens": tokens})
    be = _resolve_backend(cfg, backend, quantizer)
    qz = be.quantizer
    pcstr = param_constraint if param_constraint is not None else (lambda t: t)
    cstr = constraint if constraint is not None else (lambda t, kind="residual": t)

    if cfg.family == "decoder":
        cache = state.cache
        lengths = cache.lengths
        positions = lengths[:, None]  # (B, 1) — each row at its own position
        nk, nv = transformer._layer_bins(qz, cfg.num_layers)

        def body(carry, xs):
            layer_params, ck, cv, lnk, lnv = xs
            layer_params = pcstr(layer_params)
            h, new_c = _attn_decode(
                layer_params["attn"],
                common.rms_norm(carry, layer_params["norm1"], cfg.norm_eps),
                positions, (ck, cv), lnk, lnv, lengths, cfg, be,
            )
            xx = transformer.ffn_residual(
                layer_params, common.radd(carry, h), cfg, cstr)
            return xx, new_c

        x, new_kv = common.uscan(
            body, x, (params["layers"], cache.k, cache.v, nk, nv))
        new_cache = type(cache)(k=new_kv[0], v=new_kv[1], lengths=lengths + 1)
        logits = transformer.lm_logits(params, cfg, x)[:, 0]
        return logits, DecodeState(cache=new_cache, states=None)

    if cfg.family == "hybrid_ssm":
        cache = state.cache
        lengths = cache.lengths
        positions = lengths[:, None]
        n_groups = cfg.num_layers // cfg.attn_every
        nk, nv = transformer._layer_bins(qz, n_groups)
        shared = params["shared_attn"]

        def group_body(carry, xs):
            group_params, ck, cv, lnk, lnv, gstates = xs

            def mamba_body(c, lxs):
                lp, st = lxs
                lp = pcstr(lp)
                out, new_st = ssm.mamba2_decode_step(
                    lp["ssm"],
                    common.rms_norm(c, lp["norm"], cfg.norm_eps), st, cfg)
                return common.radd(c, out), new_st

            h, new_states = common.uscan(
                mamba_body, carry, (group_params, gstates))
            a, new_c = _attn_decode(
                shared["attn"],
                common.rms_norm(h, shared["norm"], cfg.norm_eps),
                positions, (ck, cv), lnk, lnv, lengths, cfg, be,
            )
            return common.radd(h, a), (new_c, new_states)

        x, (new_kv, new_states) = common.uscan(
            group_body, x,
            (params["mamba"], cache.k, cache.v, nk, nv, state.states))
        new_cache = type(cache)(k=new_kv[0], v=new_kv[1], lengths=lengths + 1)
        logits = transformer.lm_logits(params, cfg, x)[:, 0]
        return logits, DecodeState(cache=new_cache, states=new_states)

    if cfg.family == "xlstm":

        def group_body(carry, xs):
            group_params, (mstates, sstate) = xs

            def mbody(c, lxs):
                lp, st = lxs
                lp = pcstr(lp)
                out, new_st = xlstm.mlstm_block_decode(lp, c, st, cfg)
                return common.radd(c, out), new_st

            h, new_m = common.uscan(
                mbody, carry, (group_params["mlstm"], mstates))
            out, new_s = xlstm.slstm_block_decode(
                group_params["slstm"], h, sstate, cfg)
            return common.radd(h, out), (new_m, new_s)

        x, new_states = common.uscan(
            group_body, x, (params["groups"], state.states))
        logits = transformer.lm_logits(params, cfg, x)[:, 0]
        return logits, DecodeState(cache=None, states=new_states)

    raise ValueError(f"decode not defined for family {cfg.family}")


def decode_step_paged(
    params,
    cfg: ModelConfig,
    cache,  # pages.PagedKVCache
    tokens: jax.Array,  # (B, 1) int32 — one per decode slot
    active: jax.Array,  # (B,) bool — slots currently serving a request
    *,
    backend: AttentionBackend,
    write_mask: Optional[jax.Array] = None,  # (B,) bool — slot may append
    shard: Optional[ShardInfo] = None,  # pool kv-heads split over a mesh axis
) -> tuple[jax.Array, object]:
    """One decode step over the paged pool -> (logits (B, V), new cache).

    The continuous-batching hot loop: every slot advances one token, with
    the page table resolving each slot's scattered physical pages. Inactive
    slots still execute (masked to the trash page / garbage logits the
    scheduler ignores) so the step stays a single fixed-shape executable
    while requests come and go mid-flight.

    `write_mask` is the copy-on-write append guard: a slot whose mask entry
    is False keeps attending and advancing its length, but its K/V append
    is redirected to the reserved trash page. The scheduler computes the
    mask host-side from allocator refcounts (a slot owns its frontier page
    exclusively <=> refcount == 1); in correct operation every active
    slot's entry is True — the mask exists so a refcount bug corrupts only
    the misbehaving slot's own stream, never a page another request (or
    the prefix trie) is reading.
    """
    if cfg.family != "decoder":
        raise ValueError(
            f"paged decode is defined for family 'decoder', not "
            f"{cfg.family!r}")
    from repro.serving import pages as pages_lib

    x = transformer.embed_inputs(params, cfg, {"tokens": tokens})
    qz = backend.quantizer
    lengths = cache.lengths
    page_table = cache.page_table
    may_write = active if write_mask is None else active & write_mask
    positions = lengths[:, None]  # (B, 1) — each slot at its own position
    nk, nv = transformer._layer_bins(qz, cfg.num_layers)
    be = backend
    if shard is not None:
        be, nq_l, nkv_l = _shard_backend(cfg, backend, shard)
        sidx = jax.lax.axis_index(shard.axis)

    def body(carry, xs):
        layer_params, ck, cv, lnk, lnv = xs
        b = carry.shape[0]
        with jax.named_scope("qkv"):
            q, k, v = attention.project_qkv(
                layer_params["attn"],
                common.rms_norm(carry, layer_params["norm1"], cfg.norm_eps),
                positions, cfg)
            if shard is not None:
                # projection is replicated; each shard keeps its
                # contiguous head slice (q follows its GQA group) and
                # touches only its local pool shard
                q = jax.lax.dynamic_slice_in_dim(q, sidx * nq_l, nq_l,
                                                 axis=2)
                k = jax.lax.dynamic_slice_in_dim(k, sidx * nkv_l, nkv_l,
                                                 axis=2)
                v = jax.lax.dynamic_slice_in_dim(v, sidx * nkv_l, nkv_l,
                                                 axis=2)
        with jax.named_scope("kv_append"):
            new_c = be.paged_append(
                (ck, cv), k, v, lnk, lnv, page_table, lengths, may_write)
        with jax.named_scope("attend"):
            out = be.paged_attend(
                q, new_c, lnk, lnv, page_table, lengths + 1)
            if shard is not None:
                # device order == head order, so the gathered tensor is
                # bitwise the unsharded attend's output
                out = jax.lax.all_gather(out, shard.axis, axis=2,
                                         tiled=True)
            out = out.reshape(b, 1, cfg.num_heads * cfg.head_dim
                              ).astype(carry.dtype)
        with jax.named_scope("mlp"):
            h = jnp.einsum("bsk,kd->bsd", out, layer_params["attn"]["wo"])
            xx = transformer.ffn_residual(
                layer_params, common.radd(carry, h), cfg, shard=shard)
        return xx, new_c

    x, new_kv = common.uscan(
        body, x, (params["layers"], cache.k, cache.v, nk, nv))
    new_lengths = jnp.where(active, lengths + 1, lengths)
    new_cache = pages_lib.PagedKVCache(
        k=new_kv[0], v=new_kv[1], page_table=page_table,
        lengths=new_lengths)
    logits = transformer.lm_logits(params, cfg, x)[:, 0]
    return logits, new_cache


def _where_slot_axis(mask: jax.Array, new: jax.Array, old: jax.Array,
                     axis: int) -> jax.Array:
    shape = [1] * new.ndim
    shape[axis] = mask.shape[0]
    return jnp.where(mask.reshape(shape), new, old)


def mask_states(cfg: ModelConfig, active: jax.Array, new, old):
    """Per-slot select on a family's batched recurrent-state tree.

    Rows of `active` take the freshly-stepped state, the rest keep the
    previous one bit-exactly — the state-family analogue of the paged
    path's masked append (inactive slots still execute the fixed-shape
    step; this throws their garbage state update away). The slot axis per
    leaf follows `init_decode_state`'s tiling: hybrid leaves are
    (n_groups, attn_every, S, ...); xlstm mLSTM leaves (G, per-1, S, ...)
    and sLSTM leaves (G, S, ...).
    """
    if cfg.family == "hybrid_ssm":
        return jax.tree.map(
            lambda n, o: _where_slot_axis(active, n, o, 2), new, old)
    if cfg.family == "xlstm":
        new_m, new_s = new
        old_m, old_s = old
        return (
            jax.tree.map(
                lambda n, o: _where_slot_axis(active, n, o, 2), new_m, old_m),
            jax.tree.map(
                lambda n, o: _where_slot_axis(active, n, o, 1), new_s, old_s),
        )
    raise ValueError(f"no recurrent state for family {cfg.family!r}")


def decode_step_paged_hybrid(
    params,
    cfg: ModelConfig,
    cache,  # pages.PagedKVCache — the shared-attention layers' pool
    states,  # batched MambaState tree, leaves (n_groups, attn_every, S, ...)
    tokens: jax.Array,  # (B, 1) int32 — one per decode slot
    active: jax.Array,  # (B,) bool — slots currently serving a request
    *,
    backend: AttentionBackend,
    write_mask: Optional[jax.Array] = None,  # (B,) bool — slot may append
) -> tuple[jax.Array, object, object]:
    """One hybrid-SSM decode step: Mamba2 stacks on state slots, the shared
    attention block on paged quantized pages, in the same dispatch
    -> (logits (B, V), new cache, new states).

    Layer structure mirrors `decode_step`'s hybrid branch (zamba2: per
    group, `attn_every` Mamba2 layers then ONE shared attention block),
    but the attention sublayer reads/writes the paged pool exactly like
    `decode_step_paged` — trash-page-masked appends, per-slot page-table
    indirection — and the recurrent state update is masked per slot with
    `mask_states` so inactive slots keep their stored state bit-exactly.
    The pool's leading axis is `cfg.num_attn_layers` == n_groups (one
    attention instance per group), so page geometry and byte accounting
    carry over from the decoder path unchanged.
    """
    if cfg.family != "hybrid_ssm":
        raise ValueError(
            f"hybrid paged decode is defined for family 'hybrid_ssm', not "
            f"{cfg.family!r}")
    from repro.serving import pages as pages_lib

    x = transformer.embed_inputs(params, cfg, {"tokens": tokens})
    qz = backend.quantizer
    lengths = cache.lengths
    page_table = cache.page_table
    may_write = active if write_mask is None else active & write_mask
    positions = lengths[:, None]
    n_groups = cfg.num_layers // cfg.attn_every
    nk, nv = transformer._layer_bins(qz, n_groups)
    shared = params["shared_attn"]

    def group_body(carry, xs):
        group_params, ck, cv, lnk, lnv, gstates = xs

        def mamba_body(c, lxs):
            lp, st = lxs
            out, new_st = ssm.mamba2_decode_step(
                lp["ssm"], common.rms_norm(c, lp["norm"], cfg.norm_eps),
                st, cfg)
            return common.radd(c, out), new_st

        h, new_states = common.uscan(mamba_body, carry,
                                     (group_params, gstates))
        b = h.shape[0]
        with jax.named_scope("qkv"):
            q, k, v = attention.project_qkv(
                shared["attn"],
                common.rms_norm(h, shared["norm"], cfg.norm_eps),
                positions, cfg)
        with jax.named_scope("kv_append"):
            new_c = backend.paged_append(
                (ck, cv), k, v, lnk, lnv, page_table, lengths, may_write)
        with jax.named_scope("attend"):
            out = backend.paged_attend(
                q, new_c, lnk, lnv, page_table, lengths + 1)
            out = out.reshape(b, 1, cfg.num_heads * cfg.head_dim
                              ).astype(h.dtype)
        with jax.named_scope("mlp"):
            a = jnp.einsum("bsk,kd->bsd", out, shared["attn"]["wo"])
        return common.radd(h, a), (new_c, new_states)

    x, (new_kv, new_states) = common.uscan(
        group_body, x, (params["mamba"], cache.k, cache.v, nk, nv, states))
    new_lengths = jnp.where(active, lengths + 1, lengths)
    new_cache = pages_lib.PagedKVCache(
        k=new_kv[0], v=new_kv[1], page_table=page_table,
        lengths=new_lengths)
    new_states = mask_states(cfg, active, new_states, states)
    logits = transformer.lm_logits(params, cfg, x)[:, 0]
    return logits, new_cache, new_states


def decode_step_paged_tiered(
    params,
    cfg: ModelConfig,
    cache1,  # pages.PagedKVCache — full-precision (tier-1) pool
    cache2,  # pages.PagedKVCache — degraded (tier-2) pool, own page table
    tokens: jax.Array,  # (B, 1) int32 — one per decode slot
    active: jax.Array,  # (B,) bool — slots currently serving a request
    tier2: jax.Array,  # (B,) bool — slot's pages live in the tier-2 pool
    *,
    backend: AttentionBackend,
    backend2: AttentionBackend,
    write_mask: Optional[jax.Array] = None,  # (B,) bool — slot may append
    shard: Optional[ShardInfo] = None,  # pool kv-heads split over a mesh axis
) -> tuple[jax.Array, object, object]:
    """`decode_step_paged` over TWO pools: the tier-2 pool holds requests
    whose pages were recompressed to a lower-bit schedule under pool
    pressure (scheduler.DegradeConfig) -> (logits, new cache1, new cache2).

    Both pools share the slot axis: a slot's pages live in exactly one
    pool (`tier2` mask), its appends into the other pool are masked to
    that pool's trash page, and its attention output is selected per slot
    with `jnp.where`. Running both attends every step costs roughly 2x
    the attend FLOPs of one pool — the robustness price of keeping ONE
    fixed-shape executable while requests migrate tiers mid-flight
    (a per-mask-specialized dispatch would recompile on every migration).
    Slots keep a single shared `lengths` vector: positions are absolute
    and tier migration moves bytes, never the frontier.
    """
    if cfg.family != "decoder":
        raise ValueError(
            f"paged decode is defined for family 'decoder', not "
            f"{cfg.family!r}")
    from repro.serving import pages as pages_lib

    x = transformer.embed_inputs(params, cfg, {"tokens": tokens})
    lengths = cache1.lengths
    may_write = active if write_mask is None else active & write_mask
    w1 = may_write & ~tier2
    w2 = may_write & tier2
    positions = lengths[:, None]
    nk1, nv1 = transformer._layer_bins(backend.quantizer, cfg.num_layers)
    nk2, nv2 = transformer._layer_bins(backend2.quantizer, cfg.num_layers)
    be1, be2 = backend, backend2
    if shard is not None:
        be1, nq_l, nkv_l = _shard_backend(cfg, backend, shard)
        be2, _, _ = _shard_backend(cfg, backend2, shard)
        sidx = jax.lax.axis_index(shard.axis)

    def body(carry, xs):
        (layer_params, ck1, cv1, lnk1, lnv1, ck2, cv2, lnk2, lnv2) = xs
        b = carry.shape[0]
        with jax.named_scope("qkv"):
            q, k, v = attention.project_qkv(
                layer_params["attn"],
                common.rms_norm(carry, layer_params["norm1"], cfg.norm_eps),
                positions, cfg)
            if shard is not None:
                q = jax.lax.dynamic_slice_in_dim(q, sidx * nq_l, nq_l,
                                                 axis=2)
                k = jax.lax.dynamic_slice_in_dim(k, sidx * nkv_l, nkv_l,
                                                 axis=2)
                v = jax.lax.dynamic_slice_in_dim(v, sidx * nkv_l, nkv_l,
                                                 axis=2)
        with jax.named_scope("kv_append"):
            new_c1 = be1.paged_append(
                (ck1, cv1), k, v, lnk1, lnv1, cache1.page_table, lengths,
                w1)
            new_c2 = be2.paged_append(
                (ck2, cv2), k, v, lnk2, lnv2, cache2.page_table, lengths,
                w2)
        with jax.named_scope("attend"):
            out1 = be1.paged_attend(
                q, new_c1, lnk1, lnv1, cache1.page_table, lengths + 1)
            out2 = be2.paged_attend(
                q, new_c2, lnk2, lnv2, cache2.page_table, lengths + 1)
            # select per slot locally, then gather heads once
            out = jnp.where(tier2[:, None, None, None], out2, out1)
            if shard is not None:
                out = jax.lax.all_gather(out, shard.axis, axis=2,
                                         tiled=True)
            out = out.reshape(b, 1, cfg.num_heads * cfg.head_dim
                              ).astype(carry.dtype)
        with jax.named_scope("mlp"):
            h = jnp.einsum("bsk,kd->bsd", out, layer_params["attn"]["wo"])
            xx = transformer.ffn_residual(
                layer_params, common.radd(carry, h), cfg, shard=shard)
        return xx, (new_c1, new_c2)

    x, (new_kv1, new_kv2) = common.uscan(
        body, x, (params["layers"], cache1.k, cache1.v, nk1, nv1,
                  cache2.k, cache2.v, nk2, nv2))
    new_lengths = jnp.where(active, lengths + 1, lengths)
    new_cache1 = pages_lib.PagedKVCache(
        k=new_kv1[0], v=new_kv1[1], page_table=cache1.page_table,
        lengths=new_lengths)
    new_cache2 = pages_lib.PagedKVCache(
        k=new_kv2[0], v=new_kv2[1], page_table=cache2.page_table,
        lengths=new_lengths)
    logits = transformer.lm_logits(params, cfg, x)[:, 0]
    return logits, new_cache1, new_cache2


def verify_step_paged(
    params,
    cfg: ModelConfig,
    cache,  # pages.PagedKVCache
    tokens: jax.Array,  # (B, q_len) int32 — pending token + padded draft
    active: jax.Array,  # (B,) bool — slots currently serving a request
    n_fed: jax.Array,  # (B,) int32 — real tokens fed per slot (1..q_len)
    *,
    backend: AttentionBackend,
    write_mask: Optional[jax.Array] = None,  # (B,) bool — slot may append
    shard: Optional[ShardInfo] = None,  # pool kv-heads split over a mesh axis
) -> tuple[jax.Array, object]:
    """One speculative VERIFY step -> (logits (B, q_len, V), new cache).

    Scores q_len tokens per slot in one dispatch: slot i feeds its pending
    token followed by draft_len proposed tokens (padded to the static
    q_len; `n_fed[i]` marks the real ones). Per layer the q_len tokens'
    K/V are appended *optimistically* into the slot's pages
    (`paged_append_multi`; padding and non-owned slots redirect to the
    trash page) and row j then attends over cache positions
    [0, lengths[i] + j] — its own key included — via `paged_attend_multi`.
    That is exactly the key set, and bit-for-bit the accumulation, the
    plain `decode_step_paged` would produce feeding the same tokens one
    step at a time, which is what makes greedy speculative decoding
    lossless (tests/test_speculate.py).

    The returned cache's `lengths` are NOT advanced: acceptance decides
    the commit. The scheduler computes the accepted count on device
    (`speculate.accepted_counts`), advances each row's length by it, and
    rolls the rejected suffix back with `pages.pop_tokens` — pure
    bookkeeping, since rejected codes past the frontier are masked by
    every attend and overwritten by the next append.
    """
    if cfg.family != "decoder":
        raise ValueError(
            f"paged verify is defined for family 'decoder', not "
            f"{cfg.family!r}")
    from repro.serving import pages as pages_lib

    b, q_len = tokens.shape
    x = transformer.embed_inputs(params, cfg, {"tokens": tokens})
    qz = backend.quantizer
    lengths = cache.lengths
    page_table = cache.page_table
    may_write = active if write_mask is None else active & write_mask
    # (B, q_len): which fed positions are real AND writable
    valid = (jnp.arange(q_len, dtype=jnp.int32)[None, :]
             < n_fed[:, None]) & may_write[:, None]
    positions = lengths[:, None] + jnp.arange(q_len,
                                              dtype=lengths.dtype)[None, :]
    nk, nv = transformer._layer_bins(qz, cfg.num_layers)
    be = backend
    if shard is not None:
        be, nq_l, nkv_l = _shard_backend(cfg, backend, shard)
        sidx = jax.lax.axis_index(shard.axis)

    def body(carry, xs):
        layer_params, ck, cv, lnk, lnv = xs
        with jax.named_scope("qkv"):
            q, k, v = attention.project_qkv(
                layer_params["attn"],
                common.rms_norm(carry, layer_params["norm1"], cfg.norm_eps),
                positions, cfg)
            if shard is not None:
                q = jax.lax.dynamic_slice_in_dim(q, sidx * nq_l, nq_l,
                                                 axis=2)
                k = jax.lax.dynamic_slice_in_dim(k, sidx * nkv_l, nkv_l,
                                                 axis=2)
                v = jax.lax.dynamic_slice_in_dim(v, sidx * nkv_l, nkv_l,
                                                 axis=2)
        with jax.named_scope("kv_append"):
            new_c = be.paged_append_multi(
                (ck, cv), k, v, lnk, lnv, page_table, lengths, valid)
        with jax.named_scope("attend"):
            out = be.paged_attend_multi(
                q, new_c, lnk, lnv, page_table, lengths)
            if shard is not None:
                out = jax.lax.all_gather(out, shard.axis, axis=2,
                                         tiled=True)
            out = out.reshape(b, q_len, cfg.num_heads * cfg.head_dim
                              ).astype(carry.dtype)
        with jax.named_scope("mlp"):
            h = jnp.einsum("bsk,kd->bsd", out, layer_params["attn"]["wo"])
            xx = transformer.ffn_residual(
                layer_params, common.radd(carry, h), cfg, shard=shard)
        return xx, new_c

    x, new_kv = common.uscan(
        body, x, (params["layers"], cache.k, cache.v, nk, nv))
    new_cache = pages_lib.PagedKVCache(
        k=new_kv[0], v=new_kv[1], page_table=page_table, lengths=lengths)
    logits = transformer.lm_logits(params, cfg, x)
    return logits, new_cache


def init_decode_state(
    cfg: ModelConfig,
    batch: int,
    seq_len: int,
    *,
    quantizer: Optional[KVQuantizer] = None,
    backend: Optional[AttentionBackend] = None,
    prefilled=0,
    dtype=jnp.bfloat16,
) -> DecodeState:
    """Fresh decode state with an empty (or logically `prefilled`) cache.

    `prefilled` may be an int (uniform batch) or a (B,) vector (ragged).
    """
    cache = None
    if cfg.has_kv_cache:
        be = _resolve_backend(cfg, backend, quantizer)
        if isinstance(be, backends_lib.RawBackend) and be.dtype != dtype:
            be = backends_lib.RawBackend(cfg, dtype=dtype)
        cache = be.init_cache(batch, seq_len)
        from repro.cache.kvcache import per_seq_lengths

        cache = cache._replace(lengths=per_seq_lengths(prefilled, batch))
    states = None
    if cfg.family == "hybrid_ssm":
        n_groups = cfg.num_layers // cfg.attn_every
        one = ssm.init_mamba_state(batch, cfg, dtype)
        states = jax.tree.map(
            lambda t: jnp.tile(t[None, None],
                               (n_groups, cfg.attn_every) + (1,) * t.ndim),
            one,
        )
    if cfg.family == "xlstm":
        per = cfg.slstm_every
        n_groups = cfg.num_layers // per
        m_one = xlstm.init_mlstm_state(batch, cfg)
        s_one = xlstm.init_slstm_state(batch, cfg)
        mstates = jax.tree.map(
            lambda t: jnp.tile(t[None, None],
                               (n_groups, per - 1) + (1,) * t.ndim), m_one)
        sstates = jax.tree.map(
            lambda t: jnp.tile(t[None], (n_groups,) + (1,) * t.ndim), s_one)
        states = (mstates, sstates)
    return DecodeState(cache=cache, states=states)
