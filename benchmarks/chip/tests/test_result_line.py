"""The last line's schema, and which metrics a cell reports."""
import json
from types import SimpleNamespace

import pytest

import harness
import trace_reduce


def fake_outcome(platform="tpu"):
    w = harness.Window(
        seconds=10.0, out_tokens=1000, attempted=20, failed=0,
        completed=[SimpleNamespace(ttft_s=0.1 * i, tpot_s=0.01 * i,
                                   admitted_s=0.001 * i, tokens=[1] * 9)
                   for i in range(1, 21)],
        results=[], stats={"decode_steps": 200}, compiles=0,
        decode_tokens=900, decode_contexts=900 * 3000,
        prefills=[(3000, 2048)] * 20,
        model=harness._json(harness.HERE / "configs" / "qwen3-0.6b.json"),
        quant=None, peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        setup_s=42.0, memory_peak=12 * 2**30, memory_limit=16 * 2**30,
        trace=trace_reduce.DeviceTrace(1, 9.0, 9.5, {"qattn_paged": 6.0,
                                                     "fusion.1": 3.0},
                                       [(1.0, 0.2)]),
        trace_s=10.0)
    w.quant = w.model["serving"]["kv_quant"]
    checks = {"short_requests": {"value": 0, "limit": 0},
              "logit_gap": {"value": 0.001, "limit": 0.01}}
    device = {"platform": platform, "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": w.memory_peak, "busy_s": 9.0,
              "window_s": 10.0}
    return harness.Outcome(w, checks, device, {}, [1, 2])


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("name", ["qwen3-0.6b.doc-decode",
                                  "deepseek-7b-l8.reason-decode"])
def test_schema(name, trace):
    cell = harness.load_cell(name)
    line = harness.result_line(cell, fake_outcome(), trace)
    line = json.loads(json.dumps(line))
    assert list(line)[-1] == "compared"
    for k in ("correct", "attempted", "failed", "metrics", "device"):
        assert k in line
    assert line["correct"] is True
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(line["device"])
    want = {m["name"] for m in (cell.per_layer if trace
                                else cell.end_to_end)}
    assert set(line["metrics"]) == want
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    if trace:
        b = line["breakdown"]
        assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    for name_, c in line["compared"].items():
        assert set(c) == {"value", "limit"}


def test_shares_stay_under_100_percent():
    cell = harness.load_cell("qwen3-0.6b.doc-decode")
    m = harness.result_line(cell, fake_outcome(), True)["metrics"]
    assert 0 < m["qattn_roofline"]["value"] <= 100
    assert 0 < m["decode_mfu"]["value"] <= 100


def test_a_failed_comparison_is_not_correct():
    cell = harness.load_cell("qwen3-0.6b.doc-decode")
    out = fake_outcome()
    out.checks["logit_gap"]["value"] = 0.5
    assert harness.result_line(cell, out, False)["correct"] is False


def test_cpu_run_is_refused():
    cell = harness.load_cell("qwen3-0.6b.doc-decode")
    with pytest.raises(harness.NotADevice):
        harness.result_line(cell, fake_outcome("cpu"), False)


def test_unknown_device_has_no_peaks():
    import peaks

    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v9000")
