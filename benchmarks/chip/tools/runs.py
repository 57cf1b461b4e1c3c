#!/usr/bin/env python3
"""Run cells several times, one process per run, and collect the results.

    python3 benchmarks/chip/tools/runs.py --out chiprun_out/x.jsonl \
        --seconds 30 qwen3-0.6b.doc-decode:101,102,103 \
        qwen3-0.6b.chat-open:7,8:1

Each argument is <workload>:<seeds>[:<trace>]. This process never imports
JAX, so each child owns the chip in turn. Every run's result line (or its
failure, with the end of its standard error) is appended to --out as one
JSON object, and a summary line per run is printed. With --spread it also
prints, per cell and metric, the median and the quartile spread (first to
third quartile over the median) of the runs that were correct.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]


def spread(values: list) -> tuple[float, float]:
    med = statistics.median(values)
    if len(values) < 2:
        return med, 0.0
    q1, _, q3 = statistics.quantiles(values, n=4)
    return med, (q3 - q1) / abs(med) if med else float("nan")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--seconds", default="30")
    ap.add_argument("--timeout", type=float, default=1200)
    ap.add_argument("--spread", action="store_true")
    ap.add_argument("runs", nargs="+")
    args = ap.parse_args(argv)
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    got: dict = {}
    for spec in args.runs:
        parts = spec.split(":")
        cell, seeds = parts[0], parts[1].split(",")
        trace = parts[2] if len(parts) > 2 else "0"
        for seed in seeds:
            cmd = [sys.executable, "benchmarks/chip/run.py", "--workload",
                   cell, "--seed", seed, "--seconds", args.seconds,
                   "--trace", trace]
            t0 = time.time()
            try:
                p = subprocess.run(cmd, cwd=ROOT, capture_output=True,
                                   text=True, timeout=args.timeout)
                rc, so, se = p.returncode, p.stdout, p.stderr
            except subprocess.TimeoutExpired as e:
                rc, so, se = 124, e.stdout or "", e.stderr or ""
                so = so if isinstance(so, str) else so.decode()
                se = se if isinstance(se, str) else se.decode()
            wall = time.time() - t0
            rec = {"cell": cell, "seed": int(seed), "trace": int(trace),
                   "rc": rc, "wall_s": wall}
            lines = [ln for ln in so.splitlines() if ln.startswith("{")]
            if rc == 0 and lines:
                rec["result"] = json.loads(lines[-1])
            rec["stderr_tail"] = se[-3000:]
            with open(out, "a") as f:
                f.write(json.dumps(rec) + "\n")
            res = rec.get("result", {})
            print(f"{cell} seed {seed} trace {trace}: rc {rc} wall "
                  f"{wall:.1f} s correct {res.get('correct')} "
                  f"metrics {json.dumps(res.get('metrics'))} "
                  f"compared {json.dumps(res.get('compared'))}", flush=True)
            if rc != 0 or not lines:
                print(se[-2000:], flush=True)
            if res.get("correct") and trace == "0":
                for k, v in res["metrics"].items():
                    got.setdefault((cell, k), []).append(v["value"])
    if args.spread:
        for (cell, k), vals in sorted(got.items()):
            med, sp = spread(vals)
            print(f"spread {cell} {k}: n {len(vals)} median {med!r} "
                  f"iqr/median {sp!r}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
