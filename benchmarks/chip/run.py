#!/usr/bin/env python3
"""The chip benchmark: one run of one cell of BENCHMARK.json.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <window> --trace <0|1>

Run from the root of a checkout, on a machine that holds the TPU chips the
cell asks for; one process owns them. The last line of standard output is
one JSON object (correct, attempted, failed, metrics, device, with
--trace 1 breakdown, and last the numbers compared with their limits),
and the numbers compared are also the last lines of standard error. With
no TPU, or fewer chips than the cell asks for, or outside a checkout of
the repository, it prints no result and exits non-zero.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("run.py: no src/repro in this checkout", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    import harness

    cell = harness.load_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"run.py: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX sees {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           t_process=T_PROCESS, log=log)
    log("setup split: " + json.dumps(out.setup_split))
    log(f"sampled rids: {out.sample}")
    line = harness.result_line(cell, out, bool(args.trace))
    harness.print_checks(out)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
