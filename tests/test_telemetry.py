"""Observability spine (ISSUE 8): metrics registry semantics, trace ring
bounds, stats-as-registry-views equivalence on real scheduler runs (both
quant backends), Prometheus/Perfetto export, the HTTP/SSE front-end's
bitwise token parity and disconnect-cancel path, and bitwise +
dispatch-count identity when the tracer is disabled."""
import json
import time

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import mixedkv, rates
from repro.core.quantizer import KVQuantizer, QuantizerConfig
from repro.models import transformer
from repro.serving import backends as backends_lib
from repro.serving import pages as pages_lib
from repro.serving import prefix as prefix_lib
from repro.serving import scheduler, server, telemetry


def _cfg():
    return ModelConfig(name="tel", family="decoder", num_layers=2,
                       d_model=64, num_heads=4, num_kv_heads=2, d_ff=64,
                       vocab_size=128, head_dim=32)


def _qz(cfg):
    return KVQuantizer(QuantizerConfig(
        head_dim=cfg.head_dim, schedule=mixedkv.uniform(cfg.num_layers),
        k_norm=rates.NORM_K8, v_norm=rates.NORM_V4_LOG,
        storage="bitpack"))


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    qz = _qz(cfg)
    params, _ = transformer.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, qz, params


def _sched(**kw):
    base = dict(num_slots=2, page_size=4, num_pages=48, max_context=40,
                prefill_chunk=8, max_burst=4, debug_conservation=True)
    base.update(kw)
    return scheduler.SchedulerConfig(**base)


def _requests(n, seed=0, plen_hi=14, budget_hi=6):
    rng = np.random.default_rng(seed)
    return [scheduler.Request(
        rid=i,
        tokens=rng.integers(0, 128, rng.integers(2, plen_hi + 1)
                            ).astype(np.int32),
        max_new_tokens=int(rng.integers(1, budget_hi + 1)))
        for i in range(n)]


# ------------------------------------------------------------- registry ----
def test_registry_counter_gauge_semantics():
    reg = telemetry.MetricsRegistry()
    c = reg.counter("reqs", help="requests")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("free")
    g.set(10)
    g.dec(3)
    g.inc(1)
    assert g.value == 8
    # get-or-create returns the same instance; kind mismatch is an error
    assert reg.counter("reqs") is c
    with pytest.raises(ValueError):
        reg.gauge("reqs")
    # labeled series are distinct
    a = reg.counter("fin", status="ok")
    b = reg.counter("fin", status="shed")
    a.inc(2)
    assert b.value == 0 and a.value == 2


def test_histogram_bucket_correctness():
    reg = telemetry.MetricsRegistry()
    h = reg.histogram("lat", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    st = h.state()
    assert st["buckets"] == [0.1, 1.0, 10.0]
    assert st["counts"] == [1, 2, 1, 1]  # last slot = +Inf overflow
    assert st["count"] == 5
    assert st["sum"] == pytest.approx(56.05)
    # boundary lands in its own bucket (le semantics: v <= bound)
    h.observe(0.1)
    assert h.state()["counts"][0] == 2
    with pytest.raises(ValueError):
        reg.histogram("bad", buckets=(1.0, 1.0))
    # Prometheus rendering is cumulative and parses back
    text = reg.render_prometheus()
    parsed = telemetry.parse_prometheus(text)
    assert parsed['repro_lat_bucket{le="0.1"}'] == 2
    assert parsed['repro_lat_bucket{le="1"}'] == 4
    assert parsed['repro_lat_bucket{le="+Inf"}'] == 6
    assert parsed["repro_lat_count"] == 6


def test_registry_delta_views():
    reg = telemetry.MetricsRegistry()
    c = reg.counter("steps")
    h = reg.histogram("t", buckets=(1.0,))
    c.inc(3)
    h.observe(0.5)
    snap = reg.snapshot()
    c.inc(2)
    h.observe(2.0)
    d = reg.delta(snap)
    assert d.value("steps") == 2  # delta, not cumulative
    hd = d.hist("t")
    assert hd["count"] == 1 and hd["counts"] == [0, 1]
    assert hd["sum"] == pytest.approx(2.0)


# -------------------------------------------------------------- tracer -----
def test_trace_ring_bounds_and_perfetto_schema():
    tr = telemetry.Tracer(capacity=16)
    tr.reset_epoch()
    for i in range(100):
        with tr.region("work", tick=i):
            pass
    evs = tr.events()
    assert len(evs) == 16  # ring-bounded
    assert tr.dropped == 84 and tr.emitted == 100
    assert evs[-1]["args"]["tick"] == 99  # newest survive
    doc = tr.to_perfetto()
    assert telemetry.validate_trace(doc) == []
    assert doc["otherData"]["dropped"] == 84
    # disabled tracer costs nothing and records nothing
    off = telemetry.Tracer(capacity=16, enabled=False)
    with off.region("x") as r:
        pass
    off.instant("y")
    assert r.elapsed >= 0.0  # still timed with the ring off
    assert off.events() == [] and off.emitted == 0
    with pytest.raises(ValueError):
        telemetry.Tracer(capacity=4)  # below the floor


# ------------------------------------------ spans on the profiler clock ----
def _profiled_host_events(trace_dir, names) -> list:
    """(name, start_ns, end_ns, args) of the host events named one of
    `names` in the one .xplane.pb a `jax.profiler.trace(trace_dir)`
    session wrote."""
    from jax.profiler import ProfileData

    paths = list(trace_dir.glob("**/*.xplane.pb"))
    assert len(paths) == 1, paths
    out = []
    for plane in ProfileData.from_file(str(paths[0])).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name not in names:
                    continue
                out.append((ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return out


@pytest.mark.parametrize("enabled", [True, False])
def test_region_ring_only_when_enabled(tmp_path, enabled):
    """A region always writes its span into a running profiler session;
    the ring gets it only when the tracer is enabled, with the args set at
    exit as well."""
    tr = telemetry.Tracer(capacity=16, enabled=enabled)
    with jax.profiler.trace(str(tmp_path)):
        with tr.region("outer-span", rid=7) as outer:
            with tr.region("inner-span", tid=2, k=3) as inner:
                inner.set(emitted=5)
        with tr.region("tick-span", step_num=4, tick=4):
            pass
    evs = {name: (t0, t1, args) for name, t0, t1, args
           in _profiled_host_events(
               tmp_path, ("outer-span", "inner-span", "tick-span"))}
    assert {"outer-span", "inner-span", "tick-span"} <= set(evs)
    assert evs["outer-span"][2]["rid"] == 7
    assert evs["inner-span"][2]["k"] == 3
    assert "emitted" not in evs["inner-span"][2]  # exit args: ring only
    assert evs["tick-span"][2]["step_num"] == 4
    o0, o1, _ = evs["outer-span"]
    i0, i1, _ = evs["inner-span"]
    assert o0 <= i0 and i1 <= o1
    assert 0.0 < inner.elapsed <= outer.elapsed
    ring = tr.events()
    if not enabled:
        assert ring == [] and tr.emitted == 0
        return
    assert [e["name"] for e in ring] == ["inner-span", "outer-span",
                                         "tick-span"]
    assert ring[0]["args"] == {"k": 3, "emitted": 5} and ring[0]["tid"] == 2
    assert ring[1]["dur"] == pytest.approx(outer.elapsed)
    assert telemetry.validate_trace(tr.to_perfetto()) == []


def test_engine_spans_on_profiler_clock(setup, tmp_path):
    """A profiled run of a tiny paged engine (tracer ring off, as the
    benchmark runs it) writes the scheduler's spans into the profile,
    nested tick > admit > prefill-chunk > dispatch/readback and
    tick > decode-burst > dispatch/readback/emit."""
    cfg, qz, params = setup
    eng = scheduler.PagedServingEngine(
        params, cfg, backends_lib.QuantXLABackend(cfg, qz),
        _sched(telemetry=False))
    eng.run(_requests(2, seed=9))  # compile outside the profile
    with jax.profiler.trace(str(tmp_path)):
        _, stats = eng.run(_requests(3, seed=9))
    names = ("tick", "admit", "prefill-chunk", "decode-burst", "dispatch",
             "readback", "emit")
    evs = _profiled_host_events(tmp_path, names)
    assert {e[0] for e in evs} == set(names)

    def parent(ev):
        holders = [p for p in evs if p is not ev and p[1] <= ev[1]
                   and ev[2] <= p[2] and p[2] - p[1] > ev[2] - ev[1]]
        return max(holders, key=lambda p: (p[1], -p[2]))[0] \
            if holders else None

    want = {"tick": {None}, "admit": {"tick"},
            "prefill-chunk": {"admit"}, "decode-burst": {"tick"},
            "emit": {"decode-burst"},
            "readback": {"prefill-chunk", "decode-burst"},
            "dispatch": {"prefill-chunk", "decode-burst", "tick"}}
    for ev in evs:
        assert parent(ev) in want[ev[0]], (ev, parent(ev))
    ticks = [e for e in evs if e[0] == "tick"]
    assert sorted(e[3]["step_num"] for e in ticks) == list(range(len(ticks)))
    bursts = [e for e in evs if e[0] == "decode-burst"]
    assert len(bursts) == stats["decode_bursts"] > 0
    kinds = {e[3]["kind"] for e in evs if e[0] == "dispatch"}
    assert {"decode", "prefill", "rng_split"} <= kinds
    assert all(e[3]["rid"] in range(3) for e in evs if e[0] == "admit")


@pytest.fixture(scope="module")
def warmed(setup):
    """A prefix-sharing engine with every step variant compiled ahead of
    time, the shared-prefix ones included."""
    cfg, qz, params = setup
    eng = scheduler.PagedServingEngine(
        params, cfg, backends_lib.QuantXLABackend(cfg, qz),
        _sched(prefix_cache="share", prefix_pages=2, max_context=16))
    eng.warmup(skips=(0, eng.sched.prefill_chunk))
    return eng


def test_step_executables_named(warmed):
    """Each warmed step executable carries its own module name, so the
    profile's module line tells decode bursts, prefills and prefix loads
    apart."""
    eng = warmed
    chunk = eng.sched.prefill_chunk
    width = eng.sched.max_pages
    name = lambda key: eng.executable(key).as_text().split("\n", 1)[0]
    assert name(("decode", width)).startswith("HloModule jit_decode_burst,")
    assert name(("prefill", chunk, 0)).startswith("HloModule jit_prefill,")
    n = chunk // eng.sched.page_size
    assert name(("prefix_load", n)).startswith("HloModule jit_prefix_load,")


def test_decode_executable_carries_scopes(warmed):
    """The decode burst's ops carry the step body's named scopes in their
    metadata: encode-on-write (`kv_append`) and attention (`attend`) can be
    read off a device trace by scope."""
    text = warmed.executable(("decode", warmed.sched.max_pages)).as_text()
    assert "jit(decode_burst)/while/body/" in text
    for scope in ("qkv", "kv_append", "attend", "mlp", "lm_head",
                  "sample"):
        assert f"/{scope}/" in text, scope


def test_host_syncs_counted_once(setup):
    """stats["perf"]["host_sync_count"] is the registry's cumulative
    host_syncs; the per-run host time and burst count are registry deltas
    too."""
    cfg, qz, params = setup
    eng = scheduler.PagedServingEngine(
        params, cfg, backends_lib.QuantXLABackend(cfg, qz), _sched())
    _, s1 = eng.run(_requests(3, seed=6))
    _, s2 = eng.run(_requests(3, seed=6))
    reg = eng.telemetry.registry
    assert s2["perf"]["host_sync_count"] == reg.counter("host_syncs").value
    # one readback per admission and one per decode burst
    per_run = s2["perf"]["host_sync_count"] - s1["perf"]["host_sync_count"]
    assert per_run == 3 + s2["decode_bursts"]
    assert s1["decode_bursts"] == s2["decode_bursts"] > 0
    assert 0.0 < s2["sched_host_s"] < s2["wall_s"]
    assert 0.0 < s2["prefill_wall_s"] < s2["wall_s"]


# ------------------------------------------- stats as registry views -------
@pytest.mark.parametrize("backend_kind", ["quant-xla", "quant-pallas"])
def test_stats_are_registry_views(setup, backend_kind):
    """A full scheduler run's stats[...] equal the registry deltas and the
    Prometheus exposition EXACTLY, on both quant backends."""
    cfg, qz, params = setup
    be = (backends_lib.QuantXLABackend(cfg, qz)
          if backend_kind == "quant-xla"
          else backends_lib.QuantPallasBackend(cfg, qz, interpret=True))
    eng = scheduler.PagedServingEngine(params, cfg, be, _sched(
        speculate=True, draft_len=3))
    results, stats = eng.run(_requests(4, seed=7))
    parsed = telemetry.parse_prometheus(
        eng.telemetry.registry.render_prometheus())
    # fresh engine: cumulative registry == this run's deltas
    assert parsed["repro_decode_steps_total"] == stats["decode_steps"]
    assert parsed["repro_new_tokens_total"] == stats["new_tokens"]
    assert parsed["repro_prefill_chunks_total"] == stats["prefill_chunks"]
    assert (parsed["repro_prefill_tokens_total"]
            == stats["prefill_tokens_computed"])
    assert (parsed['repro_requests_finished_total{status="completed"}']
            == stats["slo"]["completed"] == len(results))
    assert (parsed["repro_spec_draft_proposed_total"]
            == stats["spec"]["draft_proposed"])
    assert (parsed["repro_spec_draft_accepted_total"]
            == stats["spec"]["draft_accepted"])
    assert parsed["repro_ttft_seconds_count"] == stats["ttft_hist"]["count"]
    assert (parsed["repro_ttft_seconds_sum"]
            == pytest.approx(stats["ttft_hist"]["sum"]))
    assert parsed["repro_tpot_seconds_count"] == stats["tpot_hist"]["count"]
    # histograms observe completed requests only
    assert stats["ttft_hist"]["count"] == len(results)
    # end-of-run gauges: pool drained, nothing pending
    assert (parsed['repro_pool_free_pages{tier="1"}']
            == eng.sched.num_pages - 1)
    assert parsed["repro_slots_active"] == 0
    assert parsed["repro_post_warmup_variants"] == \
        stats["perf"]["post_warmup_variants"]
    # slo counters are views too
    for key, metric in (("shed", "repro_sched_shed_total"),
                        ("spills", "repro_sched_spills_total"),
                        ("degraded", "repro_sched_degraded_total")):
        assert parsed[metric] == stats["slo"][key]


def test_second_run_keeps_registry_cumulative(setup):
    """Registry counters accumulate across run() calls (Prometheus
    semantics) while stats stay per-run deltas."""
    cfg, qz, params = setup
    be = backends_lib.QuantXLABackend(cfg, qz)
    eng = scheduler.PagedServingEngine(params, cfg, be, _sched())
    _, s1 = eng.run(_requests(3, seed=1))
    _, s2 = eng.run(_requests(3, seed=1))
    assert s1["decode_steps"] == s2["decode_steps"]  # same trace, same work
    reg = eng.telemetry.registry
    cum = reg.counter("decode_steps").value
    assert cum == s1["decode_steps"] + s2["decode_steps"]


def test_request_timeline_and_tpot(setup):
    cfg, qz, params = setup
    be = backends_lib.QuantXLABackend(cfg, qz)
    eng = scheduler.PagedServingEngine(params, cfg, be, _sched())
    results, _ = eng.run(_requests(3, seed=2, budget_hi=5))
    for r in results:
        labels = [name for name, _ in r.timeline]
        assert labels[0] == "arrival" and labels[-1] == "done"
        assert "admit" in labels and "first_token" in labels
        times = [t for _, t in r.timeline]
        assert times == sorted(times)  # monotone lifecycle
        assert r.tpot_s >= 0.0
        if len(r.tokens) > 1:
            # tpot excludes the prefill-sampled first token
            assert r.tpot_s == pytest.approx(
                (r.latency_s - r.ttft_s) / (len(r.tokens) - 1))


def test_telemetry_disabled_bitwise_and_dispatch_identical(setup):
    """sched.telemetry=False: same tokens BITWISE, same dispatch/host-sync
    counts, and an empty trace ring — instrumentation must cost the hot
    loop nothing it can observe."""
    cfg, qz, params = setup
    reqs = _requests(4, seed=3)
    runs = {}
    for flag in (True, False):
        be = backends_lib.QuantXLABackend(cfg, qz)
        eng = scheduler.PagedServingEngine(
            params, cfg, be, _sched(telemetry=flag))
        results, stats = eng.run(list(reqs))
        runs[flag] = (results, stats, eng)
    on_res, on_stats, on_eng = runs[True]
    off_res, off_stats, off_eng = runs[False]
    for a, b in zip(on_res, off_res):
        np.testing.assert_array_equal(a.tokens, b.tokens)
        assert a.host_sync_count == b.host_sync_count
    assert on_stats["decode_steps"] == off_stats["decode_steps"]
    assert (on_stats["perf"]["jit_variants_compiled"]
            == off_stats["perf"]["jit_variants_compiled"])
    assert (on_stats["perf"]["host_sync_count"]
            == off_stats["perf"]["host_sync_count"])
    # tracer off -> empty ring; metrics stay on (host-side arithmetic)
    assert off_eng.telemetry.tracer.events() == []
    assert len(on_eng.telemetry.tracer.events()) > 0
    # counter views identical (per_class excluded: wall-clock latencies)
    drop = lambda s: {k: v for k, v in s.items() if k != "per_class"}
    assert drop(off_stats["slo"]) == drop(on_stats["slo"])


def test_scheduler_trace_spans(setup):
    """Tick spans carry tids (slot lanes), rids, and wall durations; the
    export validates against the Perfetto schema."""
    cfg, qz, params = setup
    be = backends_lib.QuantXLABackend(cfg, qz)
    eng = scheduler.PagedServingEngine(params, cfg, be, _sched())
    eng.run(_requests(3, seed=4))
    evs = eng.telemetry.tracer.events()
    names = {e["name"] for e in evs}
    assert {"run-start", "admit", "prefill-chunk", "decode-burst",
            "run-end"} <= names
    admits = [e for e in evs if e["name"] == "admit"]
    assert all(e["tid"] >= 1 and "rid" in e["args"] for e in admits)
    assert telemetry.validate_trace(eng.telemetry.tracer.to_perfetto()) \
        == []


def test_watchdog_error_ships_trace_tail(setup):
    cfg, qz, params = setup
    be = backends_lib.QuantXLABackend(cfg, qz)
    eng = scheduler.PagedServingEngine(
        params, cfg, be, _sched(max_wall_s=1e-4))
    with pytest.raises(scheduler.SchedulerWatchdogError) as exc:
        eng.run(_requests(2, seed=5))
    tail = exc.value.diagnostic["trace_tail"]
    assert tail, "watchdog diagnostic must carry the flight recorder"
    assert tail[-1]["name"] == "watchdog"
    assert tail[-1]["args"]["max_wall_s"] == pytest.approx(1e-4)


# ------------------------------------------------------ prefix eviction ----
def test_prefix_eviction_reasons_split():
    """LRU turnover during insert vs scheduler pool-pressure reclaim are
    distinguishable; the total stays backwards-compatible."""
    tel = telemetry.Telemetry(enabled=True, trace_capacity=64)
    alloc = pages_lib.PageAllocator(num_pages=32)
    trie = prefix_lib.PrefixTrie(alloc, page_size=2, max_pages=2,
                                 telemetry=tel)
    rng = np.random.default_rng(0)
    for i in range(3):  # 3 distinct 2-token blocks through a 2-node bound
        toks = np.asarray([i, i], np.int32)
        ids = alloc.alloc(1, owner=("req", i))
        trie.insert(toks, np.asarray(ids, np.int32))
    assert trie.evictions_lru == 1 and trie.evictions_reclaim == 0
    assert trie.evict_one()
    assert trie.evictions_reclaim == 1
    assert trie.evictions == trie.evictions_lru + trie.evictions_reclaim
    st = trie.stats()
    assert st["evictions"] == 2
    assert st["evictions_lru"] == 1 and st["evictions_reclaim"] == 1
    parsed = telemetry.parse_prometheus(
        tel.registry.render_prometheus())
    assert parsed['repro_prefix_evictions_total{reason="lru"}'] == 1
    assert parsed['repro_prefix_evictions_total{reason="reclaim"}'] == 1
    names = [e["name"] for e in tel.tracer.events()]
    assert names.count("prefix-evict") == 2
    for i in range(3):
        alloc.release(("req", i))


def test_prefix_stats_delta_in_scheduler_run(setup):
    """stats['prefix'] carries the per-run eviction-reason split."""
    cfg, qz, params = setup
    be = backends_lib.QuantXLABackend(cfg, qz)
    eng = scheduler.PagedServingEngine(params, cfg, be, _sched(
        prefix_cache="share", prefix_pages=4))
    shared = np.arange(8, dtype=np.int32) % 128
    reqs = [scheduler.Request(
        rid=i, tokens=np.concatenate([shared, [100 + i, 101 + i]]
                                     ).astype(np.int32),
        max_new_tokens=3) for i in range(3)]
    _, stats = eng.run(reqs)
    px = stats["prefix"]
    assert {"evictions_lru", "evictions_reclaim"} <= set(px)
    assert px["evictions"] == px["evictions_lru"] + px["evictions_reclaim"]
    assert px["hits"] + px["misses"] == len(reqs)


# ---------------------------------------------------------- HTTP server ----
@pytest.fixture(scope="module")
def frontend(setup):
    cfg, qz, params = setup
    be = backends_lib.QuantXLABackend(cfg, qz)
    eng = scheduler.PagedServingEngine(params, cfg, be, _sched())
    fe = server.HTTPFrontend(eng)
    fe.start()
    yield fe, eng
    if fe._engine_thread.is_alive():
        fe.stop()


def test_sse_stream_bitwise_identical_to_result(setup, frontend):
    """Streamed SSE tokens == the typed RequestResult == a fresh
    in-process engine's tokens for the same prompt, bitwise."""
    cfg, qz, params = setup
    fe, eng = frontend
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, 128, 9).tolist()
    events = list(server.sse_generate(
        fe.port, {"prompt": prompt, "max_new_tokens": 5}))
    streamed = [t for ev, d in events if ev == "tokens"
                for t in d["tokens"]]
    res_doc = next(d for ev, d in events if ev == "result")
    assert streamed == res_doc["tokens"] and len(streamed) == 5
    typed = next(r for r in fe.results() if r.rid == res_doc["rid"])
    assert streamed == [int(t) for t in typed.tokens]
    # bitwise parity with a fresh batch-mode engine on the same prompt
    be2 = backends_lib.QuantXLABackend(cfg, qz)
    eng2 = scheduler.PagedServingEngine(params, cfg, be2, _sched())
    ref, _ = eng2.run([scheduler.Request(
        rid=0, tokens=np.asarray(prompt, np.int32), max_new_tokens=5)])
    np.testing.assert_array_equal(np.asarray(streamed), ref[0].tokens)


def test_http_metrics_trace_healthz(frontend):
    fe, eng = frontend
    parsed = telemetry.parse_prometheus(
        server.http_get(fe.port, "/metrics"))
    assert 'repro_pool_free_pages{tier="1"}' in parsed
    doc = json.loads(server.http_get(fe.port, "/trace"))
    assert telemetry.validate_trace(doc) == []
    h = json.loads(server.http_get(fe.port, "/healthz"))
    assert h["ok"] and h["engine_alive"]
    assert h["pool"]["total"] == eng.sched.num_pages - 1


def test_http_bad_request_is_400(frontend):
    fe, _ = frontend
    import urllib.error
    import urllib.request
    body = json.dumps({"prompt": [], "max_new_tokens": 4}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{fe.port}/generate", data=body,
        headers={"Content-Type": "application/json"})
    with pytest.raises(urllib.error.HTTPError) as exc:
        urllib.request.urlopen(req, timeout=30)
    assert exc.value.code == 400


def test_disconnect_triggers_cancel_and_frees_pages(frontend):
    """A mid-stream client disconnect lands as an engine cancel: the
    request retires with status='cancelled' and every page returns to
    the pool."""
    fe, eng = frontend
    rng = np.random.default_rng(12)
    prompt = rng.integers(0, 128, 8).tolist()
    n_before = len(fe.results())
    list(server.sse_generate(
        fe.port, {"prompt": prompt, "max_new_tokens": 30},
        disconnect_after=1))
    deadline = time.monotonic() + 60
    while True:
        done = fe.results()[n_before:]
        if done and eng.allocator.num_free == eng.sched.num_pages - 1:
            break
        assert time.monotonic() < deadline, \
            f"cancel did not land: free={eng.allocator.num_free}"
        time.sleep(0.05)
    assert done[-1].status == "cancelled"
    assert 0 < len(done[-1].tokens) < 30  # partial progress retained


def test_http_shutdown_returns_run_stats(frontend):
    fe, eng = frontend
    stats = fe.stop()
    assert stats is not None
    assert stats["slo"]["cancelled"] >= 1  # the disconnect test's cancel
    assert eng.allocator.num_free == eng.sched.num_pages - 1


# ---------------------------------------------- family / state metrics ----
def _state_engine(arch_id, seed=0, **sched_kw):
    from repro.configs import registry

    cfg = registry.get_reduced_config(arch_id)
    params, _ = transformer.init_params(jax.random.PRNGKey(seed), cfg)
    if cfg.has_kv_cache:
        be = backends_lib.QuantXLABackend(cfg, KVQuantizer(QuantizerConfig(
            head_dim=cfg.head_dim,
            schedule=mixedkv.uniform(cfg.num_attn_layers),
            k_norm=rates.NORM_K8, v_norm=rates.NORM_V4_LOG,
            storage="bitpack")))
    else:
        be = backends_lib.RawBackend(cfg)
    eng = scheduler.PagedServingEngine(params, cfg, be, _sched(**sched_kw))
    return cfg, params, eng


def _state_requests(cfg, n, seed=0, plen=10, budget=5, **kw):
    rng = np.random.default_rng(seed)
    return [scheduler.Request(
        rid=i, tokens=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
        max_new_tokens=budget, **kw) for i in range(n)]


def test_family_stats_block_and_state_metrics():
    """stats['family'] names the adapter and its capabilities; the state
    cache exports its footprint as a gauge (packed bytes resident) and
    its codec cost as a counter (encode wall seconds), both registry
    views of the same run."""
    cfg, params, eng = _state_engine("xlstm-350m")
    results, stats = eng.run(_state_requests(cfg, 3, seed=3))
    assert all(r.status == "completed" for r in results)
    fam = stats["family"]
    assert fam["name"] == "xlstm" and fam["state_slots"]
    assert not (fam["paged_kv"] or fam["speculate"] or fam["prefix_share"]
                or fam["degrade"] or fam["mesh"])
    parsed = telemetry.parse_prometheus(
        eng.telemetry.registry.render_prometheus())
    assert parsed["repro_state_cache_bytes"] \
        == fam["state_cache_bytes"] == eng.store.physical_bytes(eng.states)
    assert fam["state_cache_bytes"] > 0
    assert parsed["repro_state_encode_seconds_total"] \
        == pytest.approx(fam["state_encode_seconds"])
    assert fam["state_encode_seconds"] > 0
    # decoder engines carry the same block with state caps off
    cfgd, qzd = _cfg(), None
    paramsd, _ = transformer.init_params(jax.random.PRNGKey(0), cfgd)
    engd = scheduler.PagedServingEngine(
        paramsd, cfgd, backends_lib.QuantXLABackend(cfgd, _qz(cfgd)),
        _sched())
    _, statsd = engd.run(_requests(1, seed=1))
    famd = statsd["family"]
    assert famd["name"] == "decoder" and famd["paged_kv"]
    assert not famd["state_slots"]
    assert "state_cache_bytes" not in famd


def test_state_family_trace_spans():
    """A hybrid run under preemption emits the state lifecycle as spans:
    state-prefill on admission, state-spill / state-restore around the
    preemption, all carrying slot lanes + rids and passing the Perfetto
    schema check."""
    cfg, params, eng = _state_engine(
        "zamba2-2.7b", preempt=True, max_wall_s=300.0)
    rng = np.random.default_rng(11)

    def req(rid, budget, arrival, priority):
        return scheduler.Request(
            rid=rid, tokens=rng.integers(0, cfg.vocab_size, 10)
            .astype(np.int32), max_new_tokens=budget,
            arrival=arrival, priority=priority)

    results, stats = eng.run(
        [req(0, 12, 0.0, 0), req(1, 12, 0.0, 0), req(2, 5, 0.02, 1)])
    assert stats["slo"]["spills"] >= 1
    evs = eng.telemetry.tracer.events()
    names = {e["name"] for e in evs}
    assert {"state-prefill", "state-spill", "state-restore"} <= names
    for name in ("state-prefill", "state-spill", "state-restore"):
        spans = [e for e in evs if e["name"] == name]
        assert spans and all(
            e["tid"] >= 1 and "rid" in e["args"] for e in spans), name
    assert telemetry.validate_trace(eng.telemetry.tracer.to_perfetto()) == []
