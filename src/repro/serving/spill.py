"""Page spill / restore: preemption's memory mechanics.

Angular quantization makes a page *position-independent packed bytes*:
every token row is a fixed number of bits with no calibration state, no
inter-page pointers, and absolute positions live in the page TABLE, not
the payload. Spilling a live request is therefore a pure byte move:

  spill    gather the request's pages out of the device pool into host
           numpy (`spill_pages`), release the page references
           (exclusive pages return to the free list; shared prefix pages
           survive on their co-owners' refcounts), clear the slot.
  restore  allocate fresh pages (any ids — the payload does not care),
           upload the bytes (`restore_pages`), rewrite the page-table
           row, and resume decoding from the same pending token. The
           codes are bit-identical, the attend paths mask by length
           exactly as before, so the resumed request's greedy tokens are
           bitwise the tokens it would have produced uninterrupted
           (tests/test_preempt.py pins this on both quant backends).

Tier migration (`migrate_pages`) is the other pressure rung: dequantize a
victim's pages through its quantizer and re-encode them into a pool built
for a lower-bit `MixedKVSchedule` (narrower packed words -> genuinely
smaller pages). That path is lossy by design — the scheduler records it
per-request and the quality floor bounds how far it may drop.

Shape discipline: gathers/scatters are bucketed to pow-2 page counts
(padding indexes the reserved trash page 0), so XLA's eager-op cache
holds O(log pool) executables per pool shape instead of one per spill
size. These ops live on the pressure path — admission-time, not the
decode hot loop — and do not route through the engine's `_dispatch`
variant accounting.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving import telemetry


def _pow2_pad_ids(page_ids: np.ndarray) -> np.ndarray:
    """Pad a page-id vector to the next power of two with trash-page 0s."""
    n = max(1, len(page_ids))
    b = 1
    while b < n:
        b *= 2
    out = np.zeros((b,), np.int32)
    out[:len(page_ids)] = page_ids
    return out


class SpilledPages:
    """Host-side copy of one request's packed pages (all layers, K + V).

    `k`/`v` are QuantizedKV trees of numpy arrays shaped
    (L, n_pages, n_kv, page_size, ...) — the exact pool slices, bytes
    untouched. `n_pages` is the REAL page count (the arrays may be padded
    to a power of two; padded rows are trash-page garbage)."""

    def __init__(self, k, v, n_pages: int):
        self.k = k
        self.v = v
        self.n_pages = int(n_pages)

    def nbytes(self) -> int:
        return int(sum(a.nbytes for a in (*self.k, *self.v)))


def spill_pages(pool, page_ids: np.ndarray,
                tracer: telemetry.Tracer = telemetry.NULL_TRACER
                ) -> SpilledPages:
    """Device -> host copy of `page_ids` out of a paged pool.

    `pool` is any object with QuantizedKV `.k`/`.v` pool trees of arrays
    (L, P, n_kv, page_size, X). Returns the packed payload; the caller
    releases the page references afterwards (the bytes here are a copy,
    not a view). The "spill-copy" span of `tracer` covers the device->host
    transfer."""
    ids = _pow2_pad_ids(np.asarray(page_ids, np.int32))
    with tracer.region("spill-copy", pages=len(page_ids),
                       bucket=len(ids)) as span:
        idx = jnp.asarray(ids)
        k = jax.tree.map(lambda a: np.asarray(a[:, idx]), pool.k)
        v = jax.tree.map(lambda a: np.asarray(a[:, idx]), pool.v)
        out = SpilledPages(k, v, len(page_ids))
        span.set(bytes=out.nbytes())
    return out


@functools.partial(jax.jit, donate_argnums=(0,))
def _upload(pool_a, host_a, ids):
    # donated: the upload rewrites pool pages in place instead of copying
    # the whole pool per restore event
    return pool_a.at[:, ids].set(host_a.astype(pool_a.dtype))


def restore_pages(pool, spilled: SpilledPages, new_ids: np.ndarray,
                  tracer: telemetry.Tracer = telemetry.NULL_TRACER):
    """Host -> device upload of a spilled payload into freshly allocated
    pages. `new_ids` must have exactly `spilled.n_pages` entries; the ids
    need not match the original ones (pages are position-independent).
    Returns the new pool (buffers donated-in-spirit via jit; the caller
    replaces its pool reference). Padded payload rows scatter into the
    trash page 0 — duplicate trash writes are unordered but the trash
    page holds no data by contract. The "restore-copy" span of `tracer`
    covers the host->device upload."""
    new_ids = np.asarray(new_ids, np.int32)
    if len(new_ids) != spilled.n_pages:
        raise ValueError(
            f"restore needs {spilled.n_pages} pages, got {len(new_ids)}")
    padded = _pow2_pad_ids(new_ids)
    with tracer.region("restore-copy", pages=spilled.n_pages,
                       bucket=len(padded), bytes=spilled.nbytes()):
        ids = jnp.asarray(padded)
        k = jax.tree.map(lambda a, h: _upload(a, jnp.asarray(h), ids),
                         pool.k, spilled.k)
        v = jax.tree.map(lambda a, h: _upload(a, jnp.asarray(h), ids),
                         pool.v, spilled.v)
    return pool._replace(k=k, v=v)


@dataclasses.dataclass
class SpilledRequest:
    """Everything needed to resume a preempted request bit-for-bit.

    The packed pages (`payload`), the slot's host control-plane state
    (generated tokens, pending token, lengths, the on-device-drafting
    context stream), and the accounting counters that must survive the
    round trip. `n_pages` is the FULL reservation (span worst case), of
    which the first `pages_with_data` actually hold tokens — restore
    re-reserves the full count so the resumed request can never OOM
    mid-flight, exactly like a fresh admission.
    """

    req: object  # scheduler.Request
    priority: int
    generated: list
    next_tok: int
    length: int
    ctx: np.ndarray  # (ctx_len,) prompt + emitted tokens (pending last)
    payload: SpilledPages  # the pages_with_data data pages (None when
    #   the family holds no pages — pure-recurrent xlstm)
    n_pages: int  # full span reservation to re-allocate on restore
    tier2: bool  # payload lives in the degraded (tier-2) pool
    t_admit: float
    t_first: float
    # state-slot families (serving/statecache.py): the slot's PACKED
    # quantized state bytes, snapshotted host-side — restore re-uploads
    # them bit-exactly. None for page-only (decoder) families.
    state: object = None
    # carried accounting
    draft_proposed: int = 0
    draft_accepted: int = 0
    verify_steps: int = 0
    host_syncs: int = 0
    preemptions: int = 0
    spill_count: int = 0
    restore_retries: int = 0
    degraded: bool = False
    # per-request timeline marks (name, t) carried across the round trip
    # so RequestResult.timeline spans preemptions
    marks: list = dataclasses.field(default_factory=list)
    # transient-failure backoff: do not retry before this trace time
    not_before: float = 0.0

    @property
    def rid(self) -> int:
        return self.req.rid


def migrate_pages(pool1, page_ids: np.ndarray, qz1, qz2, pool2,
                  new_ids: np.ndarray, migrate_fn=None):
    """Recompress pages from a tier-1 pool into a lower-bit tier-2 pool.

    Gathers `page_ids` from `pool1`, dequantizes through `qz1`, re-encodes
    through `qz2` (same norm configs / head_dim / Hadamard seed; only the
    angle schedule differs), and scatters into `new_ids` of `pool2`.
    Lossy by one requantization — the degradation rung's price. Returns
    the new pool2. `migrate_fn` (built by `make_migrate_fn`) carries the
    jitted compute; passing it explicitly lets the engine cache one per
    pow-2 page-count bucket."""
    ids1 = _pow2_pad_ids(np.asarray(page_ids, np.int32))
    ids2 = _pow2_pad_ids(np.asarray(new_ids, np.int32))
    if len(ids1) != len(ids2):  # same real count -> same pow-2 bucket
        raise ValueError("migrate: page-id vectors bucket differently")
    fn = migrate_fn if migrate_fn is not None else make_migrate_fn(qz1, qz2)
    k2, v2 = fn(pool1.k, pool1.v, jnp.asarray(ids1), pool2.k, pool2.v,
                jnp.asarray(ids2))
    return pool2._replace(k=k2, v=v2)


def make_migrate_fn(qz1, qz2):
    """jit'd (pool1_k, pool1_v, ids, pool2_k, pool2_v, new_ids) ->
    (new pool2_k, pool2_v): the dequant -> requant tier migration.

    Layer codebook sizes broadcast as (L, 1, 1, 1, 1) against the gathered
    (L, n, n_kv, page_size, ...) pool slices — one executable serves every
    layer, the same broadcast `fake_quant_layers` uses. One compile per
    pow-2 page-count bucket (the ids' static shape)."""
    nk1, nv1 = qz1.config.schedule.as_arrays()
    nk2, nv2 = qz2.config.schedule.as_arrays()

    def bc(n):  # (L,) -> (L, 1, 1, 1, 1) broadcast over pool slices
        return jnp.asarray(n).reshape(-1, 1, 1, 1, 1)

    def tier_migrate(p1k, p1v, ids, p2k, p2v, new_ids):
        def requant(pool_a_tree, n1, n2, norm_cfg, dst_tree):
            g = jax.tree.map(lambda a: a[:, ids], pool_a_tree)
            x = qz1.decode(g, n1, norm_cfg)
            c = qz2.encode(x, n2, norm_cfg)
            return jax.tree.map(
                lambda d, s: d.at[:, new_ids].set(s.astype(d.dtype)),
                dst_tree, c)

        k2 = requant(p1k, bc(nk1), bc(nk2), qz1.config.k_norm, p2k)
        v2 = requant(p1v, bc(nv1), bc(nv2), qz1.config.v_norm, p2v)
        return k2, v2

    return jax.jit(tier_migrate, donate_argnums=(3, 4))
