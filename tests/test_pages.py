"""Paged KV cache: allocator properties (hypothesis), page write/gather
round-trips, paged-vs-contiguous bitwise attend parity through both
quant backends, and the paged kernels' kv-head blocking: its block size
and bitwise parity with one-head calls and the verify-row expansion."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.base import ModelConfig
from repro.core import mixedkv, packing, rates
from repro.core.quantizer import KVQuantizer, QuantizerConfig
from repro.kernels.qattn import qattn as qattn_k
from repro.serving import backends as backends_lib
from repro.serving import pages


def _cfg(**kw):
    base = dict(name="pg", family="decoder", num_layers=2, d_model=64,
                num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                head_dim=32)
    base.update(kw)
    return ModelConfig(**base)


def _qz(cfg, storage="bitpack"):
    return KVQuantizer(QuantizerConfig(
        head_dim=cfg.head_dim, schedule=mixedkv.uniform(cfg.num_layers),
        k_norm=rates.NORM_K8, v_norm=rates.NORM_V4_LOG, storage=storage))


# ------------------------------------------------ allocator properties -----
@settings(max_examples=25, deadline=None)
@given(num_pages=st.integers(4, 64), seed=st.integers(0, 10_000))
def test_allocator_no_aliasing_and_conservation(num_pages, seed):
    """Random alloc/free interleavings: live requests never share a page,
    page 0 is never handed out, and free+live always partition 1..P-1."""
    rng = np.random.default_rng(seed)
    alloc = pages.PageAllocator(num_pages)
    live: dict[int, set] = {}
    for step in range(40):
        if live and rng.uniform() < 0.4:
            victim = int(rng.choice(list(live)))
            n = alloc.free(victim)
            assert n == len(live.pop(victim))
        else:
            rid = step
            n = int(rng.integers(1, max(2, num_pages // 3)))
            if not alloc.can_alloc(n):
                with pytest.raises(RuntimeError):
                    alloc.alloc(n, rid)
                continue
            got = alloc.alloc(n, rid)
            assert len(got) == n
            assert 0 not in got
            for owned in live.values():
                assert not (owned & set(got.tolist()))
            live[rid] = set(got.tolist())
        alloc.check_conservation()
        assert alloc.num_free + alloc.num_live == num_pages - 1
        # without `share` every live page has exactly one reference
        # (refcounted sharing itself is covered by tests/test_prefix.py)
        assert alloc.total_refs == alloc.num_live


def test_allocator_reuses_freed_pages_first():
    alloc = pages.PageAllocator(16)
    a = alloc.alloc(3, "a")
    b = alloc.alloc(2, "b")
    alloc.free("a")
    c = alloc.alloc(3, "c")  # LIFO: the just-freed pages come back
    assert set(c.tolist()) == set(a.tolist())
    alloc.free("b")
    alloc.free("c")
    assert alloc.num_free == 15
    alloc.check_conservation()


def test_allocator_rejects_degenerate_pools():
    with pytest.raises(ValueError):
        pages.PageAllocator(1)  # only the trash page
    alloc = pages.PageAllocator(4)
    with pytest.raises(ValueError):
        alloc.alloc(-1, "x")
    with pytest.raises(RuntimeError):
        alloc.alloc(4, "x")  # page 0 reserved -> only 3 allocatable


def test_pages_for_tokens_and_per_page_valid():
    assert pages.pages_for_tokens(0, 8) == 0
    assert pages.pages_for_tokens(1, 8) == 1
    assert pages.pages_for_tokens(8, 8) == 1
    assert pages.pages_for_tokens(9, 8) == 2
    with pytest.raises(ValueError):
        pages.pages_for_tokens(-1, 8)
    assert pages.per_page_valid(13, 4, 8).tolist() == [8, 5, 0, 0]


# ------------------------------------------------ pool init / accounting ---
def test_init_rejects_sliding_window_and_tiny_pools():
    cfg = _cfg(sliding_window=8)
    with pytest.raises(ValueError):
        pages.init_paged_cache(cfg, _qz(cfg), 8, 4, 2, 2)
    cfg = _cfg()
    with pytest.raises(ValueError):
        pages.init_paged_cache(cfg, _qz(cfg), 1, 4, 2, 2)


def test_pool_payload_bytes_matches_token_accounting():
    """cache_physical_bytes of the pool == num_pages * page_payload_bytes
    (and token_payload_bytes agrees with what the arrays actually store)."""
    cfg = _cfg()
    qz = _qz(cfg)
    num_pages, ps = 6, 4
    pool = pages.init_paged_cache(cfg, qz, num_pages, ps, 2, 3)
    got = pages.cache_physical_bytes(pool)
    assert got == num_pages * pages.page_payload_bytes(qz, cfg, ps)
    # storage="uint8" fallback accounting stays consistent too
    c = qz.config
    assert packing.token_payload_bytes(
        c.n_pairs, c.index_width, 8, "uint8") == c.n_pairs + c.n_pairs + 8


# ------------------------------------------------ write / append / gather --
def _scatter_rows(pool_q, codes_q, pt, ps):
    """Scatter contiguous per-row codes (B, T, ...) into pool pages."""
    b, mp = pt.shape

    def put(pool_a, codes_a):
        resh = pages.tokens_to_pages(codes_a, ps, axis=1)
        return pool_a.at[jnp.asarray(pt)].set(resh.astype(pool_a.dtype))

    return jax.tree.map(put, pool_q, codes_q)


def test_write_prompt_pages_roundtrips_through_gather():
    cfg = _cfg()
    qz = _qz(cfg)
    ps, n_pages = 4, 3
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(
        size=(cfg.num_layers, n_pages * ps, cfg.num_kv_heads, cfg.head_dim)),
        jnp.float32)
    codes = qz.encode(x, 128, qz.config.k_norm)  # (L, T, nkv, ...)
    pool = pages.init_paged_cache(cfg, qz, 8, ps, 1, n_pages)
    ids = np.asarray([5, 2, 7], np.int32)  # deliberately out of order
    written = pages.write_prompt_pages(pool.k, codes, jnp.asarray(ids), ps)
    table = jnp.asarray(ids[None])  # (1, 3)
    layer0 = jax.tree.map(lambda a: a[0], written)
    dense = pages.gather_pages(layer0, table)
    for got, want in zip(jax.tree.leaves(dense), jax.tree.leaves(codes)):
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(want[0]))


def test_append_token_pages_offsets_and_trash_redirect():
    cfg = _cfg()
    qz = _qz(cfg)
    ps = 4
    pool = pages.init_paged_cache(cfg, qz, 8, ps, 2, 2)
    layer = jax.tree.map(lambda a: a[0], pool.k)
    rng = np.random.default_rng(1)
    new = qz.encode(jnp.asarray(
        rng.normal(size=(2, 1, cfg.num_kv_heads, cfg.head_dim)),
        jnp.float32), 128, qz.config.k_norm)
    pt = jnp.asarray([[3, 6], [5, 1]], jnp.int32)
    lengths = jnp.asarray([5, 2], jnp.int32)  # -> (page 6, off 1), (5, 2)
    active = jnp.asarray([True, False])
    out = pages.append_token_pages(layer, new, pt, lengths, active, ps)
    # active row 0 landed at physical page 6, offset 1
    np.testing.assert_array_equal(np.asarray(out.indices[6, :, 1]),
                                  np.asarray(new.indices[0, 0]))
    # inactive row 1 went to the trash page 0, NOT its table page 5
    assert (np.asarray(out.indices[5]) == 0).all()
    assert (np.asarray(out.indices[0, :, 0]) ==
            np.asarray(new.indices[1, 0])).all()


# ------------------------------------------------ attend parity ------------
# id: (kv heads, q heads, page size, pages per slot, per-slot lengths,
#      kv heads per grid step). Every slot's page table names the trash
#      page 0 past its frontier, as the scheduler's does.
PAGED_GEOMETRIES = {
    "nkv2-ps8": (2, 4, 8, 3, [5, 17, 24], 2),
    # an empty slot, and pages past the frontier
    "nkv4-ps8-dead": (4, 8, 8, 3, [0, 5, 24], 4),
    # 128-token pages: two blocks of 2 kv heads per page
    "nkv4-ps128-dead": (4, 8, 128, 3, [0, 200, 384], 2),
    "nkv4-ps128-mha": (4, 4, 128, 3, [0, 200, 384], 2),
}
# At one query row per kv head (MHA) and 128-token pages the Pallas
# interpreter's fused verify walk differs from the verify_rows expansion
# in the last bit, with one head a step as with blocks of heads; compiled
# for a TPU v5e the two agree bit for bit (ROADMAP, Queue 1, item 4). The
# multi-query tests take the GQA geometries.
MULTI_GEOMETRIES = [g for g in PAGED_GEOMETRIES if not g.endswith("mha")]


def _paged_case(geometry, storage="bitpack", seed=7):
    """Random K/V encoded and scattered into one layer's pool, plus the
    contiguous codes, logical queries and a page table whose entries past
    each slot's frontier name the trash page."""
    nkv, nq, ps, mp, lengths, hb = PAGED_GEOMETRIES[geometry]
    cfg = _cfg(num_heads=nq, num_kv_heads=nkv)
    qz = _qz(cfg, storage)
    b, t = len(lengths), mp * ps
    rng = np.random.default_rng(seed)
    k, v = (jnp.asarray(rng.normal(size=(b, t, nkv, cfg.head_dim)),
                        jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.normal(size=(b, 1, nq, cfg.head_dim)), jnp.float32)
    kq = qz.encode(k, 128, qz.config.k_norm)
    vq = qz.encode(v, 64, qz.config.v_norm)
    pool = pages.init_paged_cache(cfg, qz, 1 + b * mp + 2, ps, b, mp)
    pt = rng.permutation(np.arange(1, 1 + b * mp)).reshape(b, mp)
    pt = pt.astype(np.int32)
    layer_k = _scatter_rows(jax.tree.map(lambda a: a[0], pool.k), kq, pt, ps)
    layer_v = _scatter_rows(jax.tree.map(lambda a: a[0], pool.v), vq, pt, ps)
    live = np.arange(mp)[None, :] * ps < np.asarray(lengths)[:, None]
    table = jnp.asarray(np.where(live, pt, 0))
    assert qattn_k.default_head_block(
        jax.tree.leaves((layer_k, layer_v))) == hb
    return dict(cfg=cfg, qz=qz, q=q, kq=kq, vq=vq, layer=(layer_k, layer_v),
                table=table, lengths=jnp.asarray(lengths, jnp.int32))


@pytest.mark.parametrize("geometry", list(PAGED_GEOMETRIES))
@pytest.mark.parametrize("storage", ["bitpack", "uint8"])
def test_paged_attend_bitwise_matches_contiguous_both_backends(storage,
                                                               geometry):
    """Scattered pages + page-table indirection reproduce the contiguous
    cache attend BIT-FOR-BIT on both backends: quant-pallas (block_t ==
    page_size) and quant-xla (gather materialization) — with one or
    several blocks of kv heads per page, empty slots and dead pages."""
    c = _paged_case(geometry, storage)
    cfg, qz, q, table, n_valid = (c["cfg"], c["qz"], c["q"], c["table"],
                                  c["lengths"])
    ps = c["layer"][0].indices.shape[2]

    pallas = backends_lib.QuantPallasBackend(cfg, qz, interpret=True,
                                             block_t=ps)
    got = pallas.paged_attend(q, c["layer"], 128, 64, table, n_valid)
    want = pallas.attend(q, (c["kq"], c["vq"]), 128, 64, n_valid)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    # quant-xla softmaxes an empty slot's all-masked row into an average
    # of whatever its positions hold (the trash page here, the contiguous
    # codes there), so it is compared on the slots that hold a token
    held = np.asarray(n_valid) > 0
    xla = backends_lib.QuantXLABackend(cfg, qz, y_dtype=jnp.float32)
    got_x = xla.paged_attend(q, c["layer"], 128, 64, table, n_valid)
    want_x = xla.attend(q, (c["kq"], c["vq"]), 128, 64, n_valid)
    np.testing.assert_array_equal(np.asarray(got_x)[held],
                                  np.asarray(want_x)[held])
    # and the two backends agree with each other numerically
    np.testing.assert_allclose(np.asarray(got)[held],
                               np.asarray(got_x)[held],
                               rtol=1e-3, atol=1e-3)


def _kernel_operands(c):
    """What `ops.paged_attend_*_quant_cache_op` hands the bit-packed
    kernels: the 8 pool leaves and the static storage geometry."""
    qz = c["qz"]
    kc, vc = qz.config.k_norm, qz.config.v_norm
    pool = tuple(jax.tree.leaves(c["layer"]))
    static = dict(n_bins_k=128, n_bins_v=64, idx_bits=qz.config.index_width,
                  k_bits=kc.bits, k_log=kc.log_space,
                  k_nq_packed=qz.config.norm_packed(kc), v_bits=vc.bits,
                  v_log=vc.log_space, v_nq_packed=qz.config.norm_packed(vc),
                  interpret=True)
    return pool, static


def _q_rot(c, rows, seed=11):
    b, nkv = c["table"].shape[0], c["cfg"].num_kv_heads
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(b, nkv, rows, c["qz"].config.d_pad)),
                       jnp.float32)


@pytest.mark.parametrize("geometry", MULTI_GEOMETRIES)
def test_paged_qattn_multi_bitwise_matches_verify_rows(geometry):
    """The fused verify walk over blocks of kv heads equals, bit for bit,
    the single-query kernel run on the `verify_rows` expansion — empty
    slots and dead pages included."""
    c = _paged_case(geometry)
    pool, static = _kernel_operands(c)
    b, g, q_len = c["table"].shape[0], c["cfg"].q_per_kv, 3
    nkv, dp = c["cfg"].num_kv_heads, c["qz"].config.d_pad
    # committed lengths leave room for the q_len rows in the slot's pages
    lengths = jnp.maximum(c["lengths"] - q_len, 0)
    q_rot = _q_rot(c, q_len * g)
    got = qattn_k.paged_qattn_multi(q_rot, *pool, c["table"], lengths,
                                    q_len=q_len, g=g, **static)

    rows_table, rows_len = qattn_k.verify_rows(c["table"], lengths, q_len)
    q_rows = q_rot.reshape(b, nkv, q_len, g, dp).transpose(0, 2, 1, 3, 4)
    want = qattn_k.paged_qattn(q_rows.reshape(b * q_len, nkv, g, dp), *pool,
                               rows_table, rows_len, **static)
    want = want.reshape(b, q_len, nkv, g, dp).transpose(0, 2, 1, 3, 4)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(want.reshape(b, nkv, q_len * g, dp)))


@pytest.mark.parametrize("geometry, q_len",
                         [(g, 1) for g in PAGED_GEOMETRIES]
                         + [(g, 3) for g in MULTI_GEOMETRIES])
def test_paged_kernels_head_blocks_match_one_head_calls(geometry, q_len):
    """Blocking kv heads changes no bit: the kernels over all n_kv heads
    equal one call per head (a one-head pool, so one head per grid
    step)."""
    c = _paged_case(geometry)
    pool, static = _kernel_operands(c)
    g = c["cfg"].q_per_kv
    q_rot = _q_rot(c, q_len * g)
    if q_len == 1:
        def call(q, leaves):
            return qattn_k.paged_qattn(q, *leaves, c["table"], c["lengths"],
                                       **static)
    else:
        # committed lengths leave room for the q_len rows in the pages
        lengths = jnp.maximum(c["lengths"] - q_len, 0)

        def call(q, leaves):
            return qattn_k.paged_qattn_multi(
                q, *leaves, c["table"], lengths, q_len=q_len, g=g, **static)
    got = call(q_rot, pool)
    one_head = [call(q_rot[:, h:h + 1], [a[:, h:h + 1] for a in pool])
                for h in range(c["cfg"].num_kv_heads)]
    np.testing.assert_array_equal(np.asarray(got),
                                  np.concatenate(one_head, axis=1))


@pytest.mark.parametrize("n_kv", [8, 32, 2])
def test_default_head_block_fits_padded_tiles(n_kv):
    """The benchmark's models at page 128, head_dim 128, bit-packed under
    the early-boost schedule: a head-page's tiles take 416 KiB of VMEM
    once padded to (sublane, 128-lane) tiles, so 2 heads (1.625 MiB
    double buffered) fit the 2 MiB budget and 4 do not — qwen3-0.6b (8
    kv heads), deepseek-llm-7b (32) and a 4-chip shard of qwen3-0.6b (2
    kv heads) all get 2."""
    cfg = ModelConfig(name="hb", family="decoder", num_layers=28,
                      d_model=1024, num_heads=2 * n_kv, num_kv_heads=n_kv,
                      d_ff=3072, vocab_size=1024, head_dim=128)
    qz = KVQuantizer(QuantizerConfig(
        head_dim=128, schedule=mixedkv.early_boost(28, 4),
        k_norm=rates.NORM_K8, v_norm=rates.NORM_V4_LOG, storage="bitpack"))
    pool = jax.eval_shape(lambda: pages.init_paged_cache(cfg, qz, 4, 128, 1,
                                                         1))
    leaves = [jax.ShapeDtypeStruct(a.shape[1:], a.dtype)
              for a in jax.tree.leaves((pool.k, pool.v))]
    # (128, 16) u32 index words, (128, 64) u8 K norms, (128, 32) u8 V
    # nibbles, (128, 1) f32 range scalars; each padded to 128 lanes
    assert sorted((a.shape[-1], a.dtype.itemsize) for a in leaves) == [
        (1, 4)] * 4 + [(16, 4)] * 2 + [(32, 1), (64, 1)]
    head_page = 2 * 128 * 128 * 4 + 2 * 128 * 128 + 4 * 128 * 128 * 4
    assert head_page == 416 * 1024
    budget = qattn_k.PAGED_VMEM_BUDGET
    hb = qattn_k.default_head_block(leaves)
    assert hb == 2
    assert n_kv % hb == 0 and 2 * hb * head_page <= budget
    bigger = [d for d in range(hb + 1, n_kv + 1) if n_kv % d == 0]
    assert all(2 * d * head_page > budget for d in bigger)
    # a budget below one double-buffered head-page still runs, one head a
    # step; exactly two head-pages' worth gives one head
    assert qattn_k.default_head_block(leaves, vmem_budget=1) == 1
    assert qattn_k.default_head_block(leaves, 2 * head_page) == 1
    assert qattn_k.default_head_block(leaves, 4 * head_page) == 2
    assert qattn_k.default_head_block(leaves, 16 * head_page) == min(n_kv, 8)


def test_paged_attend_ignores_garbage_in_unowned_pages():
    """Mutating pages a slot does NOT own (including the trash page) must
    not change its attend output — the indirection really is page-exact."""
    cfg = _cfg()
    qz = _qz(cfg)
    b, ps, mp = 1, 4, 2
    rng = np.random.default_rng(3)
    k = jnp.asarray(rng.normal(size=(b, mp * ps, cfg.num_kv_heads,
                                     cfg.head_dim)), jnp.float32)
    kq = qz.encode(k, 128, qz.config.k_norm)
    vq = qz.encode(k, 64, qz.config.v_norm)
    pool = pages.init_paged_cache(cfg, qz, 6, ps, b, mp)
    pt = np.asarray([[2, 4]], np.int32)
    layer_k = _scatter_rows(jax.tree.map(lambda a: a[0], pool.k), kq, pt, ps)
    layer_v = _scatter_rows(jax.tree.map(lambda a: a[0], pool.v), vq, pt, ps)
    q = jnp.asarray(rng.normal(size=(b, 1, cfg.num_heads, cfg.head_dim)),
                    jnp.float32)
    be = backends_lib.QuantPallasBackend(cfg, qz, interpret=True)
    n_valid = jnp.asarray([6], jnp.int32)
    base = be.paged_attend(q, (layer_k, layer_v), 128, 64,
                           jnp.asarray(pt), n_valid)
    # trash unowned pages 0, 1, 3, 5 with all-ones garbage
    unowned = jnp.asarray([0, 1, 3, 5])

    def vandalize(qkv):
        return type(qkv)(*[a.at[unowned].set(1) for a in qkv])
    got = be.paged_attend(q, (vandalize(layer_k), vandalize(layer_v)),
                          128, 64, jnp.asarray(pt), n_valid)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(got))
