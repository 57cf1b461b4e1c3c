"""Sharded paged serving: simulated-mesh parity + allocator lockstep.

The tentpole contract of multi-device serving (docs/sharding.md): a
`SchedulerConfig.mesh` engine splits the page pool's kv-head axis over N
devices and must emit BITWISE the greedy tokens of the mesh=None
single-device engine — on both quant backends, through chunked prefill,
burst decode, on-device speculation, and copy-on-write prefix sharing.
No real multi-chip hardware runs in CI, so the mesh is simulated:
conftest.py forces 8 host CPU devices (XLA_FLAGS before the first jax
import) and `launch.mesh.make_sim_mesh` carves 1/2/4/8-way sub-meshes
out of them. A 1-way mesh still runs the full shard_map machinery
(axis_index slicing, all-gathers, lockstep mirrors), so the parity
sweep covers both "sharding math is exact" and "collectives degenerate
correctly".

The property half: `pages.ShardedPageAllocators` keeps N mirror
allocators in lockstep by construction — a seeded stateful test drives
random alloc/share/spill/restore/release sequences against it and a
single reference allocator, asserting identical results and per-shard
conservation after every op.
"""
from __future__ import annotations

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

jax = pytest.importorskip("jax")

from repro.configs.base import ModelConfig
from repro.core import mixedkv, rates
from repro.core.quantizer import KVQuantizer, QuantizerConfig
from repro.distributed import sharding
from repro.launch import mesh as mesh_lib
from repro.models import transformer
from repro.serving import backends as backends_lib
from repro.serving import pages as pages_lib
from repro.serving import scheduler as sched_lib


def _cfg(**kw):
    base = dict(name="shard", family="decoder", num_layers=2, d_model=64,
                num_heads=8, num_kv_heads=8, d_ff=64, vocab_size=128,
                head_dim=8)
    base.update(kw)
    return ModelConfig(**base)


def _qz(cfg):
    return KVQuantizer(QuantizerConfig(
        head_dim=cfg.head_dim, schedule=mixedkv.uniform(cfg.num_layers),
        k_norm=rates.NORM_K8, v_norm=rates.NORM_V4_LOG, storage="bitpack"))


def _backend(name, cfg, qz):
    if name == "quant-pallas":
        return backends_lib.QuantPallasBackend(cfg, qz, interpret=True)
    return backends_lib.QuantXLABackend(cfg, qz)


def _trace(rng, lengths, budget=6):
    return [sched_lib.Request(
        rid=i, tokens=rng.integers(1, 127, size=int(n)).astype(np.int32),
        max_new_tokens=budget, arrival=0.0)
        for i, n in enumerate(lengths)]


def _serve(params, cfg, backend, reqs, mesh=None, warm=False, **sched_kw):
    """One engine build + one run. warm=False compiles lazily — strictly
    fewer variants than warmup(), which matters because quant-pallas
    interpret-mode traces are expensive to compile; the dispatch-
    discipline tests opt in to the full AOT/warm path explicitly."""
    sc = sched_lib.SchedulerConfig(
        num_slots=2, page_size=8, num_pages=64, max_context=64,
        prefill_chunk=8, max_burst=4, debug_conservation=True,
        max_wall_s=240.0, mesh=mesh, **sched_kw)
    eng = sched_lib.PagedServingEngine(params, cfg, backend, sc)
    if warm:
        eng.warmup()
    results, stats = eng.run(reqs)
    toks = {r.rid: tuple(int(t) for t in r.tokens) for r in results}
    return toks, stats, eng


#: canonical parity trace: sub-chunk, multi-chunk (chunked prefill),
#: page-crossing prompts — more requests than slots so admission churns
CANON = [5, 19, 11, 30]

# single-device reference runs are deterministic, so every mesh size
# diffs against ONE cached run per (backend, trace) instead of paying
# the reference compile again per parametrization
_ref_cache: dict = {}


def _reference(setup, backend_name):
    if backend_name not in _ref_cache:
        cfg, params = setup
        be = _backend(backend_name, cfg, _qz(cfg))
        reqs = _trace(np.random.default_rng(42), CANON)
        toks, stats, _ = _serve(params, cfg, be, reqs)
        _ref_cache[backend_name] = (toks, stats, reqs)
    return _ref_cache[backend_name]


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    params, _ = transformer.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


# ------------------------------------------------------------ parity -------
@pytest.mark.parametrize("backend_name", ["quant-pallas", "quant-xla"])
@pytest.mark.parametrize("n_shards", [2, 4])
def test_token_parity_vs_single_device(setup, sim_mesh_devices,
                                       backend_name, n_shards):
    """Chunked prefill + burst decode: sharded greedy tokens are bitwise
    the single-device engine's, on both quant backends."""
    if len(sim_mesh_devices) < n_shards:
        pytest.skip(f"need {n_shards} devices")
    cfg, params = setup
    ref, _, reqs = _reference(setup, backend_name)
    be = _backend(backend_name, cfg, _qz(cfg))
    got, _, eng = _serve(params, cfg, be, reqs,
                         mesh=mesh_lib.make_sim_mesh(
                             n_shards, sim_mesh_devices))
    assert got == ref
    eng.allocator.check_conservation()


def test_mesh_engine_commits_step_inputs_to_the_mesh(setup,
                                                     sim_mesh_devices):
    """With a mesh, nothing a step consumes is left on device 0: params
    are replicated over the mesh, the pool is split by kv head, and host
    (numpy) tables and device rng keys are placed replicated before each
    dispatch."""
    cfg, params = setup
    mesh = mesh_lib.make_sim_mesh(2, sim_mesh_devices)
    sc = sched_lib.SchedulerConfig(num_slots=2, page_size=8, num_pages=16,
                                   max_context=64, prefill_chunk=8,
                                   mesh=mesh)
    eng = sched_lib.PagedServingEngine(
        params, cfg, _backend("quant-xla", cfg, _qz(cfg)), sc)
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    split = jax.sharding.NamedSharding(mesh, sharding.paged_pool_pspec())
    assert all(a.sharding.is_equivalent_to(rep, a.ndim)
               for a in jax.tree.leaves(eng.params))
    assert all(a.sharding.is_equivalent_to(split, a.ndim)
               for a in jax.tree.leaves((eng.pool.k, eng.pool.v)))
    table, key = eng._on_mesh((eng.page_table, jax.random.PRNGKey(0)))
    for a in (table, key):
        assert a.sharding.is_equivalent_to(rep, a.ndim)
    assert {s.device for s in table.addressable_shards} == set(
        mesh.devices.flat)


def test_token_parity_8way_and_1way(setup, sim_mesh_devices):
    """The sweep's edges: 8-way (one kv-head per device) and 1-way (full
    shard_map machinery, degenerate collectives) both match."""
    if len(sim_mesh_devices) < 8:
        pytest.skip("need 8 devices")
    cfg, params = setup
    ref, _, reqs = _reference(setup, "quant-xla")
    be = _backend("quant-xla", cfg, _qz(cfg))
    for n in (1, 8):
        got, _, _ = _serve(params, cfg, be, reqs,
                           mesh=mesh_lib.make_sim_mesh(n, sim_mesh_devices))
        assert got == ref, f"{n}-way diverged"


def test_token_parity_gqa(setup, sim_mesh_devices):
    """Grouped-query attention: q-heads follow their kv group's shard
    (2 q-heads per kv-head here), still bitwise."""
    cfg = _cfg(num_kv_heads=4)  # 8 q-heads over 4 kv-heads
    params, _ = transformer.init_params(jax.random.PRNGKey(1), cfg)
    be = _backend("quant-xla", cfg, _qz(cfg))
    reqs = _trace(np.random.default_rng(3), [6, 17])
    ref, _, _ = _serve(params, cfg, be, reqs)
    got, _, _ = _serve(params, cfg, be, reqs,
                       mesh=mesh_lib.make_sim_mesh(2, sim_mesh_devices))
    assert got == ref


def test_token_parity_speculation(setup, sim_mesh_devices):
    """Fused on-device speculative bursts under shard_map: draft + verify
    + accept rounds emit bitwise the single-device spec engine's tokens,
    with identical draft accounting."""
    cfg, params = setup
    be = _backend("quant-xla", cfg, _qz(cfg))
    rng = np.random.default_rng(11)
    # repeated structure so drafts actually get accepted
    pat = rng.integers(1, 127, size=6).astype(np.int32)
    reqs = [sched_lib.Request(rid=i, tokens=np.tile(pat, 3),
                              max_new_tokens=8, arrival=0.0)
            for i in range(3)]
    kw = dict(speculate=True, draft_len=3)
    ref, rstats, _ = _serve(params, cfg, be, reqs, **kw)
    got, gstats, _ = _serve(params, cfg, be, reqs,
                            mesh=mesh_lib.make_sim_mesh(2, sim_mesh_devices),
                            **kw)
    assert got == ref
    for k in ("draft_proposed", "draft_accepted", "verify_steps"):
        assert gstats["spec"][k] == rstats["spec"][k]


def test_token_parity_prefix_share(setup, sim_mesh_devices):
    """Copy-on-write prefix sharing over a sharded pool: the trie maps
    pages by reference on every shard's mirror allocator; shared-suffix
    prefills stay bitwise and the hit counters agree."""
    cfg, params = setup
    be = _backend("quant-xla", cfg, _qz(cfg))
    rng = np.random.default_rng(5)
    prefix = rng.integers(1, 127, size=16).astype(np.int32)
    reqs = [sched_lib.Request(
        rid=i,
        tokens=np.concatenate(
            [prefix, rng.integers(1, 127, size=4 + i).astype(np.int32)]),
        max_new_tokens=5, arrival=float(i) * 1e-4)
        for i in range(3)]
    kw = dict(prefix_cache="share", prefix_pages=8)
    ref, rstats, _ = _serve(params, cfg, be, reqs, **kw)
    got, gstats, eng = _serve(params, cfg, be, reqs,
                              mesh=mesh_lib.make_sim_mesh(
                                  2, sim_mesh_devices), **kw)
    assert got == ref
    assert gstats["prefix"]["hits"] == rstats["prefix"]["hits"]
    assert gstats["prefix"]["hit_tokens"] == rstats["prefix"]["hit_tokens"]
    eng.allocator.check_conservation()


def test_mesh_config_validation(setup, sim_mesh_devices):
    """Non-divisible head counts and meshes without a model axis are
    loud deployment errors, not silent replication."""
    cfg, params = setup
    be = _backend("quant-xla", cfg, _qz(cfg))
    mesh4 = mesh_lib.make_sim_mesh(2, sim_mesh_devices)
    bad_cfg = _cfg(num_heads=6, num_kv_heads=3)
    bad_params, _ = transformer.init_params(jax.random.PRNGKey(2), bad_cfg)
    with pytest.raises(ValueError, match="cannot shard"):
        sched_lib.PagedServingEngine(
            bad_params, bad_cfg, _backend("quant-xla", bad_cfg, _qz(bad_cfg)),
            sched_lib.SchedulerConfig(num_pages=32, max_context=64,
                                      mesh=mesh4))
    no_model = jax.sharding.Mesh(
        np.array(sim_mesh_devices[:2]).reshape(2), ("data",))
    with pytest.raises(ValueError, match="model"):
        sched_lib.SchedulerConfig(mesh=no_model)


def test_mesh_none_keeps_legacy_dispatch(setup):
    """mesh=None engines carry no shard info and install AOT executables
    exactly as before — the dispatch-count-identity half of the
    acceptance criteria (variant enumeration unchanged, _exec populated,
    post-warmup count zero)."""
    cfg, params = setup
    be = _backend("quant-xla", cfg, _qz(cfg))
    reqs = _trace(np.random.default_rng(9), [5, 12])
    toks, stats, eng = _serve(params, cfg, be, reqs, warm=True)
    assert eng._shard is None
    assert eng._exec, "legacy path must keep AOT-compiled executables"
    assert stats["perf"]["post_warmup_variants"] == 0
    assert isinstance(eng.allocator, pages_lib.PageAllocator)


def test_mesh_warmup_dispatch_discipline(setup, sim_mesh_devices):
    """warmup() on a mesh engine (warm-by-call, not AOT) still leaves the
    serving loop with ZERO post-warmup compilations, and warming does not
    perturb parity (the no-op warm calls touch only trash page 0)."""
    cfg, params = setup
    ref, _, reqs = _reference(setup, "quant-xla")
    be = _backend("quant-xla", cfg, _qz(cfg))
    got, stats, eng = _serve(params, cfg, be, reqs, warm=True,
                             mesh=mesh_lib.make_sim_mesh(
                                 2, sim_mesh_devices))
    assert got == ref
    assert stats["perf"]["warmed"]
    assert stats["perf"]["post_warmup_variants"] == 0
    assert isinstance(eng.allocator, pages_lib.ShardedPageAllocators)


# ---------------------------------------------- allocator lockstep ---------
class _SpillModel:
    """Host-side mirror of the scheduler's spill/restore bookkeeping:
    spill releases an owner's pages but remembers the page count;
    restore re-allocates that many fresh pages for the same owner."""

    def __init__(self, alloc):
        self.alloc = alloc
        self.spilled: dict = {}

    def spill(self, owner):
        n = len(self.alloc.live_pages(owner))
        self.alloc.release(owner)
        self.spilled[owner] = n

    def restore(self, owner):
        n = self.spilled.pop(owner)
        if self.alloc.can_alloc(n):
            return self.alloc.alloc(n, owner)
        self.spilled[owner] = n
        return None


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10**6))
def test_sharded_allocator_lockstep(seed):
    """Stateful property: a random alloc/share/spill/restore/release walk
    over ShardedPageAllocators(3 shards) matches a single reference
    PageAllocator op-for-op, every shard satisfies conservation after
    every op, and the cross-shard state-equality audit passes."""
    rng = np.random.default_rng(seed)
    num_pages = int(rng.integers(6, 24))
    sharded = pages_lib.ShardedPageAllocators(num_pages, 3)
    ref = pages_lib.PageAllocator(num_pages)
    model = _SpillModel(sharded)
    owners: list = []
    spilled: set = set()
    next_owner = 0
    for _ in range(60):
        live = [o for o in owners if o not in spilled]
        op = rng.choice(["alloc", "share", "release", "release_pages",
                         "spill", "restore", "reset"],
                        p=[0.3, 0.15, 0.15, 0.1, 0.1, 0.1, 0.1])
        if op == "alloc":
            n = int(rng.integers(0, 4))
            if sharded.can_alloc(n) != ref.can_alloc(n):
                raise AssertionError("can_alloc diverged")
            if not ref.can_alloc(n):
                continue
            got = sharded.alloc(n, next_owner)
            want = ref.alloc(n, next_owner)
            assert np.array_equal(got, want)
            owners.append(next_owner)
            next_owner += 1
        elif op == "share" and live:
            src = live[int(rng.integers(len(live)))]
            pages = [p for p in set(ref.live_pages(src))
                     if p not in ref.live_pages(next_owner)]
            if not pages:
                continue
            sharded.share(pages, next_owner)
            ref.share(pages, next_owner)
            owners.append(next_owner)
            next_owner += 1
        elif op == "release" and live:
            o = live[int(rng.integers(len(live)))]
            assert sharded.release(o) == ref.release(o)
            owners.remove(o)
        elif op == "release_pages" and live:
            o = live[int(rng.integers(len(live)))]
            held = ref.live_pages(o)
            take = held[:max(1, len(held) // 2)]
            if not take:
                continue
            assert (sharded.release_pages(o, take)
                    == ref.release_pages(o, take))
            if not ref.live_pages(o):
                owners.remove(o)
        elif op == "spill" and live:
            o = live[int(rng.integers(len(live)))]
            n = len(ref.live_pages(o))
            model.spill(o)
            ref.release(o)
            spilled.add(o)
            model.spilled[o] = n  # keep counts aligned
        elif op == "restore" and spilled:
            o = sorted(spilled)[int(rng.integers(len(spilled)))]
            n = model.spilled[o]
            got = model.restore(o)
            if got is None:
                continue
            want = ref.alloc(n, o)
            assert np.array_equal(got, want)
            spilled.remove(o)
        elif op == "reset":
            sharded.reset()
            ref.reset()
            owners.clear()
            spilled.clear()
            model.spilled.clear()
        assert sharded.num_free == ref.num_free
        assert sharded.num_live == ref.num_live
        assert sharded.total_refs == ref.total_refs
        sharded.check_conservation()
    sharded.check_conservation()


def test_sharded_allocator_surfaces_divergence():
    """A shard whose state drifts (simulated by mutating one mirror
    directly) is caught by the next audited operation."""
    sh = pages_lib.ShardedPageAllocators(8, 2)
    sh.alloc(2, "a")
    sh.shards[1].alloc(1, "rogue")  # bypass the wrapper
    with pytest.raises(AssertionError, match="lockstep"):
        sh.check_conservation()
    with pytest.raises(AssertionError, match="lockstep"):
        sh.alloc(1, "b")
