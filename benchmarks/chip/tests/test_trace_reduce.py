"""trace_reduce on hand-made planes and on a small trace recorded on a
TPU v5e (tools/record_trace.py): busy time is the union of op intervals,
idle gaps are what lies between, kernel time is found by name."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import trace_reduce

DATA = Path(__file__).parent / "data" / "small_trace.xplane.pb"
BUSY_S, KERNEL_S = 0.02706323, 0.010339781  # read once, by hand-checked code


def ev(name, start_ns, dur_ns):
    return NS(name=name, start_ns=start_ns, duration_ns=dur_ns)


def plane(name, events, line="XLA Ops"):
    return NS(name=name, lines=[NS(name=line, events=events),
                                NS(name="XLA Modules",
                                   events=[ev("jit_run", 0, 10**9)])])


def test_union_overlaps_and_gaps():
    covered, gaps = trace_reduce.union([(0, 10), (5, 12), (20, 30),
                                        (30, 31), (40, 41)])
    assert covered == 12 + 11 + 1
    assert gaps == [(12, 8), (31, 9)]


def test_reduce_hand_planes():
    ops = [ev("%fusion.1 = f32[8] fusion(%qattn_paged.2)", 0, 1000),
           ev("%qattn_paged.2 = f32[8] custom-call()", 500, 1000),
           ev("%qattn_paged.7 = f32[8] custom-call()", 4000, 500),
           ev("%copy.3 = u32[4] copy(%p)", 6000, 1000),
           ev("%while.5 = (s32[]) while(%t)", 5500, 2000)]
    t = trace_reduce.reduce_planes([
        plane("/host:CPU", [ev("python", 0, 10**6)]),
        plane("/device:TPU:0", ops)])
    assert t.chips == 1
    assert t.busy_s == pytest.approx(1500e-9 + 500e-9 + 2000e-9)
    assert t.span_s == pytest.approx(7500e-9)
    assert t.time_of("qattn_paged") == pytest.approx(1500e-9)
    assert t.gaps[0] == pytest.approx((1500e-9, 2500e-9))
    b = t.breakdown()
    assert b["device_ops"][0] == ["qattn_paged", pytest.approx(1500e-9)]
    assert "while" not in dict(b["device_ops"])
    assert b["idle_gaps"][0][1] == pytest.approx(2500e-9)


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes([plane("/host:CPU", [ev("x", 0, 1)])])


@pytest.mark.skipif(not DATA.exists(), reason="no recorded trace")
def test_recorded_trace():
    """Two engine runs of a two-layer model at qwen3-0.6b's attention
    widths, 0.05 s apart, recorded on a TPU v5 lite."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(str(DATA)).planes)
    t = trace_reduce.reduce_planes(planes)
    dev = next(p for p in planes if p.name == "/device:TPU:0")
    ops = next(ln for ln in dev.lines if ln.name == "XLA Ops")
    n_kernel = sum(1 for e in ops.events
                   if trace_reduce.op_base(e.name) == "qattn_paged")
    assert t.chips == 1
    assert n_kernel > 0 and t.time_of("qattn_paged") > 0
    assert 0 < t.busy_s <= t.span_s
    assert t.time_of("qattn_paged") <= t.busy_s
    # the 0.05 s pause between the two runs is the longest idle gap
    assert t.gaps[0][1] >= 0.05
    assert t.busy_s == pytest.approx(BUSY_S, rel=1e-9)
    assert t.time_of("qattn_paged") == pytest.approx(KERNEL_S, rel=1e-9)
