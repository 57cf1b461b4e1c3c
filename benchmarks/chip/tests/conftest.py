"""Tests of the chip benchmark's own code. Run them by path:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q
"""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE)]

import pytest  # noqa: E402

def shrink(cell):
    """A cell cut to a size the CPU runs in seconds (interpret mode): the
    model to its architecture's rehearsal widths (`shrink` of its
    `arch/<arch>.py`), the traffic to a few short requests that keep the
    mix's prefix sharing and loop."""
    cell.config = cell.arch.shrink(cell.config)
    m = cell.mix
    m.pop("pool_pages", None)
    m.update(page_size=16, prefill_chunk=32)
    if "documents" in m:
        m["documents"] = [{"tokens": 64, "count": 1}]
        m["prompt"] = dict(m["prompt"], lo=4, hi=24)
    else:
        m["prompt"] = dict(m["prompt"], lo=4, hi=80)
    m["output"] = dict(m["output"], lo=4, hi=12)
    m.update(slots=4, block=8)
    if m["loop"] == "closed":
        m["backlog_blocks"] = 2
    else:
        m["rate_per_s"] = 10.0
    return cell


CELLS = ["qwen3-0.6b.doc-decode", "deepseek-7b-l8.reason-decode"]


@pytest.fixture(params=CELLS)
def tiny_cell(request):
    import harness

    return shrink(harness.load_cell(request.param))
