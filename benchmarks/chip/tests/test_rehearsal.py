"""Every cell end to end on the CPU at a tiny size, kernels in the Pallas
interpreter: warm-up covers every shape (nothing compiles in the window),
the served tokens agree with the plain reference, and the result is
refused as a device measurement."""
import pytest

import harness

SEED = 2**31 + 11  # larger than 32 signed bits hold


def test_rehearsal_serves_correctly_and_is_no_measurement(tiny_cell):
    out = harness.run_cell(tiny_cell, SEED, 2.0, False, interpret=True,
                           log=lambda m: None)
    w = out.window
    assert out.device["platform"] == "cpu"
    assert w.compiles == 0
    assert w.stats["perf"]["post_warmup_variants"] == 0
    assert w.completed and w.out_tokens > 0
    assert out.checks["short_requests"]["value"] == 0
    assert out.checks["logit_gap"]["value"] <= \
        out.checks["logit_gap"]["limit"]
    assert len(out.sample) == min(tiny_cell.mix["check_requests"],
                                  len([r for r in w.results
                                       if r.status == "completed"]))
    for m in tiny_cell.end_to_end:
        assert harness.reader(m["name"])(w) is not None, m["name"]
    with pytest.raises(harness.NotADevice):
        harness.result_line(tiny_cell, out, False)
