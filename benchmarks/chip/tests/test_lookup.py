"""A new cell needs only new files and a new BENCHMARK.json entry: in a
copy of the benchmark, a traffic mix, a limit file and a metric reader are
added, and the harness finds them by name without any existing file
changing."""
import hashlib
import json
import shutil

import harness


def digest(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_cell_is_found_by_name(tmp_path, monkeypatch):
    bench = tmp_path / "benchmarks" / "chip"
    shutil.copytree(harness.HERE, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "_trace"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = digest(bench)

    mix = harness._json(bench / "traffic" / "chat-open.json")
    mix["rate_per_s"] = 3.0
    (bench / "traffic" / "chat-slow.json").write_text(json.dumps(mix))
    (bench / "cells" / "qwen3-0.6b.chat-slow.json").write_text(
        json.dumps({"logit_gap": 0.5}))
    (bench / "metrics" / "slow_share.py").write_text(
        "def read(w):\n    return 0.25\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "qwen3-0.6b.chat-slow",
                              "config": "qwen3-0.6b", "traffic": "chat-slow",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "slow_share", "unit": "frac",
                              "better": "lower", "source": "program_counter",
                              "layer": "scheduler (serving/scheduler.py)",
                              "moves": "tpot_p95_ms",
                              "workloads": ["qwen3-0.6b.chat-slow"]})

    monkeypatch.setattr(harness, "HERE", bench)
    cell = harness.load_cell("qwen3-0.6b.chat-slow", spec)
    assert cell.mix["rate_per_s"] == 3.0
    assert cell.limits == {"logit_gap": 0.5}
    assert [m["name"] for m in cell.per_layer] == ["slow_share"]
    # metrics that list their cells skip one they do not name
    assert {m["name"] for m in cell.end_to_end} == {"tpot_p95_ms", "setup_s"}
    assert harness.reader("slow_share")(None) == 0.25
    after = digest(bench)
    assert {k: v for k, v in after.items() if k in before} == before
