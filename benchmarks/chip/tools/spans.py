#!/usr/bin/env python3
"""Trace one window of a cell and say where its time went, by the
program's own names.

    python3 benchmarks/chip/tools/spans.py --workload <cell> --seed <n> \
        --seconds <s> --out <dir> [--keep]          # on a TPU

Sets the cell up as run.py does and drives one traced window, then
reduces the profile with span_reduce.py (device seconds per step
executable and per named scope, the program's host spans, each idle gap
named after the host span that covers it) and trace_reduce.py. It writes
one JSON object to <dir>/spans.<cell>.<seed>.json and to standard output:
the reductions; the per-layer numbers they give (decode-burst device ms
per decode step, the prefill and kv_append shares of busy time, the
decode step's share of the HBM peak, the scheduler's host ms per burst);
the window's output tokens/s; and what one `Tracer.region` costs with no
profiler session. It makes no correctness check. `--keep` leaves the
profile under <dir>/trace.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]


def region_cost(n: int = 200_000) -> dict:
    """Microseconds one disabled-ring `Tracer.region` adds to a loop, with
    no profiler session open."""
    from repro.serving import telemetry

    tr = telemetry.Tracer(enabled=False)
    t = time.perf_counter()
    for _ in range(n):
        pass
    loop = time.perf_counter() - t
    t = time.perf_counter()
    for i in range(n):
        with tr.region("dispatch", kind="decode"):
            pass
    regions = time.perf_counter() - t
    return {"region_us": (regions - loop) / n * 1e6, "n": n}


def decode_step_bytes(m: dict, steps: int, tokens: int,
                      context_sum: int) -> float:
    """Mean bytes one of `steps` decode steps must read, for `tokens`
    tokens decoded over contexts adding up to `context_sum` positions:
    every matmul weight, the LM head included, once per step (the
    embedding is a gather of a few rows), plus the packed K/V codes and
    norms of each live context position at the schedule's nominal bits
    (as `roofline.qattn_work` counts them), plus the new tokens' codes."""
    import roofline

    per_param = {"bfloat16": 2, "float32": 4}[m["serving"]["param_dtype"]]
    kv = (context_sum + tokens) * roofline.kv_bytes_per_position(
        m, m["serving"]["kv_quant"])
    return roofline.matmul_params(m) * per_param + kv / steps


def per_layer(cell, sv, stats: dict, tally: dict, st) -> dict:
    """The per-layer numbers the spans give (PERF.md, section 3)."""
    steps = stats["decode_steps"]
    burst_s = sum(v for k, v in st.module_s.items()
                  if k.startswith("jit_decode_burst"))
    burst_ms = burst_s * 1e3 / steps if steps else None
    out = {
        "burst_ms_per_step.decode": burst_ms,
        "prefill_busy_share.decode": st.module_share("jit_prefill")
        + st.module_share("jit_prefix_load"),
        "kv_append_busy_share.decode": st.scope_seconds("kv_append")
        / st.busy_s,
        "sched_host_ms_per_burst.decode": stats["sched_host_s"] * 1e3
        / stats["decode_bursts"] if stats.get("decode_bursts") else None,
    }
    if burst_ms:
        nbytes = decode_step_bytes(
            cell.config, steps, tally["decode_tokens"],
            tally["decode_contexts"])
        out["decode_step_hbm_frac"] = nbytes / (
            burst_ms * 1e-3 * sv.peaks["hbm_bytes_per_s"])
        out["decode_step_bytes"] = nbytes
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--keep", action="store_true")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    import jax

    import harness
    import span_reduce
    import trace_reduce

    cell = harness.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("spans.py: needs a TPU", file=sys.stderr)
        return 1
    cost = region_cost()
    sv = harness.setup(cell, args.seed, args.seconds, t_start=t_start)
    out_dir = Path(args.out)
    trace_dir = out_dir / "trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    _, stats, tally = harness.drive_window(
        sv.engine, sv.traffic.window, args.seconds, sv.counter, trace_dir)
    path = trace_reduce.find_xplane(str(trace_dir))
    t = time.perf_counter()
    st = span_reduce.reduce_file(path)
    dt = trace_reduce.reduce_file(path)
    reduce_s = time.perf_counter() - t
    idle = sum(g for _, g, _ in st.gaps)
    doc = {
        "workload": args.workload, "seed": args.seed,
        "seconds": args.seconds, "device": jax.devices()[0].device_kind,
        "output_tok_s": tally["out_tokens"] / args.seconds,
        "window_s": tally["t_run"], "decode_steps": stats["decode_steps"],
        "decode_bursts": stats["decode_bursts"],
        "region_cost": cost,
        "per_layer": per_layer(cell, sv, stats, tally, st),
        "modules_over_busy": sum(st.module_s.values()) / st.busy_s,
        "trace_reduce_busy_s": dt.busy_s,
        "idle_s": idle,
        "idle_attributed_share": 1.0 - st.idle_by_label.get(
            "unattributed", 0.0) / idle if idle else None,
        "spans": st.summary(n=20),
        "reduce_s": reduce_s, "gc": tally["gc"],
    }
    if not args.keep:
        shutil.rmtree(trace_dir, ignore_errors=True)
    out_dir.mkdir(parents=True, exist_ok=True)
    name = out_dir / f"spans.{args.workload}.{args.seed}.json"
    name.write_text(json.dumps(doc, indent=1))
    print(json.dumps(doc), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
