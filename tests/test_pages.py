"""Paged KV cache: allocator properties (hypothesis), page write/gather
round-trips, and paged-vs-contiguous bitwise attend parity through both
quant backends."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.base import ModelConfig
from repro.core import mixedkv, packing, rates
from repro.core.quantizer import KVQuantizer, QuantizerConfig
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
@pytest.mark.parametrize("storage", ["bitpack", "uint8"])
def test_paged_attend_bitwise_matches_contiguous_both_backends(storage):
    """Scattered pages + page-table indirection reproduce the contiguous
    cache attend BIT-FOR-BIT on both backends: quant-pallas (block_t ==
    page_size) and quant-xla (gather materialization)."""
    cfg = _cfg()
    qz = _qz(cfg, storage)
    b, ps, mp = 3, 8, 3
    t = mp * ps
    rng = np.random.default_rng(7)
    k = jnp.asarray(rng.normal(size=(b, t, cfg.num_kv_heads, cfg.head_dim)),
                    jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, cfg.num_kv_heads, cfg.head_dim)),
                    jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, 1, cfg.num_heads, cfg.head_dim)),
                    jnp.float32)
    kq = qz.encode(k, 128, qz.config.k_norm)
    vq = qz.encode(v, 64, qz.config.v_norm)
    n_valid = jnp.asarray([5, 17, 24], jnp.int32)

    pool = pages.init_paged_cache(cfg, qz, 1 + b * mp + 2, ps, b, mp)
    perm = rng.permutation(np.arange(1, 1 + b * mp))
    pt = perm.reshape(b, mp).astype(np.int32)
    layer_k = _scatter_rows(jax.tree.map(lambda a: a[0], pool.k), kq, pt, ps)
    layer_v = _scatter_rows(jax.tree.map(lambda a: a[0], pool.v), vq, pt, ps)
    table = jnp.asarray(pt)

    pallas = backends_lib.QuantPallasBackend(cfg, qz, interpret=True,
                                             block_t=ps)
    got = pallas.paged_attend(q, (layer_k, layer_v), 128, 64, table, n_valid)
    want = pallas.attend(q, (kq, vq), 128, 64, n_valid)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    xla = backends_lib.QuantXLABackend(cfg, qz, y_dtype=jnp.float32)
    got_x = xla.paged_attend(q, (layer_k, layer_v), 128, 64, table, n_valid)
    want_x = xla.attend(q, (kq, vq), 128, 64, n_valid)
    np.testing.assert_array_equal(np.asarray(got_x), np.asarray(want_x))
    # and the two backends agree with each other numerically
    np.testing.assert_allclose(np.asarray(got), np.asarray(got_x),
                               rtol=1e-3, atol=1e-3)


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
