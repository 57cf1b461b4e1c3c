"""Tests of the chip benchmark's own code. Run them by path:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q
"""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE)]

import pytest  # noqa: E402

#: widths of the CPU rehearsals (interpret mode): every cell keeps its own
#: structure (GQA or MHA, q/k norms, tied head, prefix sharing, loop)
TINY = dict(num_hidden_layers=2, hidden_size=64, intermediate_size=128,
            head_dim=16, vocab_size=512)


def shrink(cell):
    """A cell cut to a size the CPU runs in seconds."""
    c = cell.config
    kv_per_q = c["num_key_value_heads"] / c["num_attention_heads"]
    c.update(TINY, num_attention_heads=4,
             num_key_value_heads=max(1, int(4 * kv_per_q)))
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
