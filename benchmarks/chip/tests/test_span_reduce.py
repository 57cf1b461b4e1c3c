"""span_reduce on hand-made XSpace planes and on small traces recorded on a
TPU v5e (tools/record_trace.py): device seconds split by step executable
and by named scope, the program's host spans with their self time, and
each idle gap named after the host span that covers it."""
from pathlib import Path

import pytest

import span_reduce as sr

DATA = Path(__file__).parent / "data"
OLD = DATA / "small_trace.xplane.pb"  # before the program named its steps
NEW = DATA / "small_trace_spans.xplane.pb"


class Plane:
    """Builds one XPlane: `event(line, name, start_ns, dur_ns, **stats)`
    adds an event whose metadata carries `stats`."""

    def __init__(self, space, name):
        self.p = space.planes.add(name=name)
        self.lines, self.meta, self.stat_ids = {}, {}, {}

    def _stat(self, name):
        if name not in self.stat_ids:
            i = len(self.stat_ids) + 1
            self.stat_ids[name] = i
            self.p.stat_metadata[i].id = i
            self.p.stat_metadata[i].name = name
        return self.stat_ids[name]

    def event(self, line, name, start_ns, dur_ns, **stats):
        if line not in self.lines:
            self.lines[line] = self.p.lines.add(name=line, id=len(self.lines))
        key = (name, tuple(sorted(stats.items())))
        if key not in self.meta:
            i = len(self.meta) + 1
            md = self.p.event_metadata[i]
            md.id, md.name = i, name
            for k, v in stats.items():
                st = md.stats.add(metadata_id=self._stat(k))
                if isinstance(v, int):
                    st.uint64_value = v
                else:
                    st.str_value = v
            self.meta[key] = i
        self.lines[line].events.add(metadata_id=self.meta[key],
                                    offset_ps=start_ns * 1000,
                                    duration_ps=dur_ns * 1000)


def synthetic():
    """Device ops of two executables with idle gaps between them, and the
    host spans that were open meanwhile (times in ns)."""
    space = sr.schema()["XSpace"]()
    dev = Plane(space, sr.DEVICE0)
    dev.event("XLA Modules", "jit_decode_burst(11)", 0, 1000)
    dev.event("XLA Modules", "jit_prefill(22)", 1500, 300)
    dev.event("XLA Modules", "jit__threefry_split(33)", 2000, 100)
    burst = dict(program_id=11)
    dev.event("XLA Ops", "%while.1 = () while()", 0, 1000, **burst,
              tf_op="jit(decode_burst)/while")
    dev.event("XLA Ops", "%fusion.2 = f32[8] fusion()", 100, 400, **burst,
              tf_op="jit(decode_burst)/while/body/kv_append/mul:")
    dev.event("XLA Ops", "%qattn_paged.3 = f32[8] custom-call()", 500, 300,
              **burst, tf_op="jit(decode_burst)/while/body/attend/qattn")
    dev.event("XLA Ops", "%copy.4 = u32[8] copy()", 1500, 200,
              program_id=22)  # no tf_op: a parameter relayout
    dev.event("XLA Ops", "%fusion.5 = f32[8] fusion()", 1700, 100,
              program_id=22, tf_op="jit(prefill)/while/body/mlp/dot:")
    dev.event("XLA Ops", "%add.6 = u32[2] add()", 2000, 100, program_id=33,
              tf_op="jit(_threefry_split)/add:")
    host = Plane(space, sr.HOST)
    thread = "python"
    host.event(thread, "tick", 900, 700, step_num=0)
    host.event(thread, "decode-burst", 950, 500)
    host.event(thread, "readback", 960, 100)
    host.event(thread, "emit", 1100, 300)
    host.event(thread, "admit", 1460, 130)
    host.event(thread, "tick", 1700, 280, step_num=1)
    host.event(thread, "dispatch", 1900, 60, kind="rng_split")
    host.event(thread, "$python_fn", 1000, 400)  # not a program span
    return space


def test_modules_and_scopes():
    t = sr.reduce_space(synthetic())
    assert t.module_s == pytest.approx({"jit_decode_burst": 1e-6,
                                        "jit_prefill": 3e-7,
                                        "jit__threefry_split": 1e-7})
    # a loop's own time is what its body leaves uncovered
    assert t.scope_s == pytest.approx({
        "jit_decode_burst/unscoped": 3e-7,
        "jit_decode_burst/kv_append": 4e-7,
        "jit_decode_burst/attend": 3e-7,
        "jit_prefill/unscoped": 2e-7,
        "jit_prefill/mlp": 1e-7,
        "jit__threefry_split/unscoped": 1e-7})
    assert t.op_s["jit_decode_burst/unscoped/while"] == pytest.approx(3e-7)
    assert t.op_s["jit_prefill/unscoped/copy"] == pytest.approx(2e-7)
    assert t.busy_s == pytest.approx(1.4e-6)
    assert t.window_s == pytest.approx(2.1e-6)
    assert sum(t.module_s.values()) == pytest.approx(t.busy_s)
    assert t.module_share("jit_prefill") == pytest.approx(3 / 14)
    assert t.scope_seconds("kv_append") == pytest.approx(4e-7)


def test_gaps_go_to_the_deepest_span_covering_most_of_them():
    t = sr.reduce_space(synthetic())
    # gap 1000-1500: emit covers 1100-1400 (3/5), decode-burst 1000-1450
    # and tick all of it: emit is the deepest over half. gap 1800-2000:
    # tick 1700-1980 covers 9/10, dispatch 1900-1960 only 3/10.
    assert [(round(t0 * 1e9), round(g * 1e9), lab)
            for t0, g, lab in t.gaps] == [(1000, 500, "emit"),
                                         (1800, 200, "tick")]
    assert t.idle_by_label == pytest.approx({"emit": 5e-7, "tick": 2e-7})
    space = synthetic()
    host = next(p for p in space.planes if p.name == sr.HOST)
    del host.lines[0].events[:]  # no span open: nothing to name
    t = sr.reduce_space(space)
    assert {lab for _, _, lab in t.gaps} == {"unattributed"}


def test_host_spans_self_time_and_depth():
    t = sr.reduce_space(synthetic())
    spans = t.spans
    assert "$python_fn" not in spans
    assert spans["tick"]["count"] == 2
    assert spans["tick"]["total_s"] == pytest.approx(9.8e-7)
    # tick 0 less decode-burst and admit; tick 1 less dispatch
    assert spans["tick"]["self_s"] == pytest.approx(
        (700 - 500 - 130 + 280 - 60) * 1e-9)
    assert spans["decode-burst"]["self_s"] == pytest.approx(1e-7)
    assert spans["dispatch/rng_split"] == spans["dispatch"]
    depth = {s.name: s.depth for s in sr.host_spans(synthetic().planes)}
    assert depth == {"tick": 0, "decode-burst": 1, "readback": 2,
                     "emit": 2, "admit": 1, "dispatch": 1}
    # tick time outside readback, per decode burst
    assert t.host_ms_per_burst == pytest.approx((980 - 100) * 1e-6)


def test_no_device_plane_is_an_error():
    space = sr.schema()["XSpace"]()
    Plane(space, sr.HOST).event("python", "tick", 0, 1)
    with pytest.raises(ValueError):
        sr.reduce_space(space)


@pytest.mark.skipif(not OLD.exists(), reason="no recorded trace")
def test_reader_agrees_with_profile_data():
    """The protobuf reader gives every XLA Ops event of the recorded trace
    the name, start and duration ProfileData gives it (ProfileData keeps
    whole nanoseconds)."""
    from jax.profiler import ProfileData

    plane = next(p for p in ProfileData.from_file(str(OLD)).planes
                 if p.name == sr.DEVICE0)
    line = next(ln for ln in plane.lines if ln.name == "XLA Ops")
    want = sorted((e.name, int(e.start_ns), int(e.duration_ns))
                  for e in line.events)
    space = sr.read_space(str(OLD))
    got = sorted((e.name, e.start_ps // 1000, e.dur_ps // 1000)
                 for e in sr.events(next(p for p in space.planes
                                         if p.name == sr.DEVICE0),
                                    "XLA Ops"))
    assert len(got) == len(want) > 20000
    assert got == want


@pytest.mark.skipif(not OLD.exists(), reason="no recorded trace")
def test_old_trace_has_one_anonymous_step():
    """Before the steps were named, every step executable was `jit_run`
    and no op carried a step-body scope."""
    t = sr.reduce_file(str(OLD))
    assert "jit_run" in t.module_s and "jit_decode_burst" not in t.module_s
    assert {k.rsplit("/", 1)[1] for k in t.scope_s} == {"unscoped"}
    assert t.spans == {}
    assert sum(t.module_s.values()) == pytest.approx(t.busy_s, rel=0.02)


@pytest.mark.skipif(not NEW.exists(), reason="no recorded trace")
def test_recorded_spans():
    """Two engine runs 0.05 s apart, recorded on a TPU v5 lite against the
    named program: the module line splits decode bursts from prefills,
    the burst's ops carry its scopes, and the scheduler's spans sit on
    the host plane on the device's clock."""
    t = sr.reduce_file(str(NEW))
    assert {"jit_decode_burst", "jit_prefill"} <= set(t.module_s)
    assert "jit_run" not in t.module_s
    assert sum(t.module_s.values()) == pytest.approx(t.busy_s, rel=0.02)
    for scope in ("qkv", "kv_append", "attend", "mlp", "lm_head"):
        assert t.scope_s.get(f"jit_decode_burst/{scope}", 0) > 0, scope
    assert t.scope_seconds("kv_append") > 0
    assert {"tick", "admit", "prefill-chunk", "decode-burst", "dispatch",
            "readback", "emit"} <= set(t.spans)
    assert t.spans["decode-burst"]["count"] > 0
    # the 0.05 s pause between the runs lies outside every span
    longest = max(t.gaps, key=lambda g: g[1])
    assert longest[1] >= 0.05 and longest[2] == "unattributed"
