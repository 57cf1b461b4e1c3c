#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate at which
completions keep up with arrivals.

    python3 benchmarks/chip/tools/sweep.py --workload qwen3-0.6b.chat-open \
        --rates 10,12,14,16 --seconds 20 --seed 5

One process, one set-up; for each rate a fresh window of the cell's own
traffic at that rate (same sizes, arrivals scaled). Prints per rate the
requests that arrived in the window, were admitted, and completed; how many
still waited for admission at the close; and the p95 of the wait for
admission and of TTFT. A rate keeps up when the queue at the close is no
longer than the slots and completions reach the arrivals less what is in
flight.
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    import jax
    import numpy as np

    import harness
    import traffic as traffic_lib

    if jax.devices()[0].platform != "tpu":
        print("sweep.py: needs a TPU", file=sys.stderr)
        return 1
    cell = harness.load_cell(args.workload)
    rates = [float(r) for r in args.rates.split(",")]
    cell.mix["rate_per_s"] = max(rates)  # the longest request list to warm
    sv = harness.setup(cell, args.seed, args.seconds)
    vocab = cell.arch.model_config(cell.config).vocab_size
    for rate in rates:
        cell.mix["rate_per_s"] = rate
        tr = traffic_lib.generate(cell.mix, vocab, args.seed, args.seconds)
        results, stats, tally = harness.drive_window(
            sv.engine, tr.window, args.seconds, sv.counter)
        t_end = tally["t_end"]
        done = [r for r in results if r.status == "completed"
                and tally["done_at"].get(r.rid, float("inf")) <= t_end]
        arrived = sum(1 for it in tr.window if it.arrival <= args.seconds)
        admitted = tally["admitted_in_window"]
        p95 = (lambda v: float(np.percentile(v, 95)) * 1e3 if v else None)
        print(json.dumps({
            "rate": rate, "arrived": arrived, "admitted": admitted,
            "completed": len(done), "queued_at_close": arrived - admitted,
            "admit_wait_p95_ms": p95([r.admitted_s for r in done]),
            "ttft_p95_ms": p95([r.ttft_s for r in done]),
            "tpot_p95_ms": p95([r.tpot_s for r in done]),
            "output_tok_s": tally["out_tokens"] / args.seconds,
            "compiles": sv.counter.n}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
