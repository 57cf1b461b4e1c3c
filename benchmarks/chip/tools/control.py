#!/usr/bin/env python3
"""Read the correctness check's two readings for a cell on the chip.

    python3 benchmarks/chip/tools/control.py --workload <cell> \
        --seeds 11,12,13 --seconds 30 [--control 0] \
        [--set '{"slots": 8}'] [--out chiprun_out/control.jsonl]

For each seed, in this one process: a run of the cell as the benchmark
makes it (a window at the cell's own load and sizes), then the widest
logit gap of the program's served tokens (the lower reading) and, unless
--control 0, of the control (the reference in three bfloat16 passes in the
program's place: the upper reading), over the same sampled requests.
Prints one JSON line per seed with both gaps and the `correct` verdict
that the benchmark's own comparison gives each (`harness.verdict`): the
program's must read true and the control's false. The line also carries
the run's end-to-end metrics and memory peak. `--set` overrides keys of
the cell's traffic mix, for trials of other sizes. The benchmark's own
runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=1)
    ap.add_argument("--set", default="{}",
                    help="JSON object of traffic-mix keys to override")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    import jax

    import harness

    if jax.devices()[0].platform != "tpu":
        print("control.py: needs a TPU", file=sys.stderr)
        return 1
    for seed in args.seeds.split(","):
        cell = harness.load_cell(args.workload)
        cell.mix.update(json.loads(args.set))
        out = harness.run_cell(cell, int(seed), args.seconds, False,
                               control=bool(args.control),
                               log=lambda m: print(m, file=sys.stderr))
        program = {k: v for k, v in out.checks.items() if k != "control_gap"}
        rec = {"cell": cell.name, "seed": int(seed), "set": args.set,
               "program_gap": out.checks["logit_gap"]["value"],
               "program_correct": harness.verdict(program),
               "sample": out.sample, "checks": out.checks,
               "metrics": {m["name"]: harness.reader(m["name"])(out.window)
                           for m in cell.end_to_end},
               "completed": len(out.window.completed),
               "memory_peak_bytes": out.device["memory_peak_bytes"],
               "setup_split": out.setup_split}
        if args.control:
            rec["control_gap"] = out.checks["control_gap"]["value"]
            rec["control_correct"] = harness.verdict(
                harness.control_checks(out.checks))
        print(json.dumps(rec), flush=True)
        if args.out:
            with open(ROOT / args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
