#!/usr/bin/env python3
"""Record the small device trace that tests/test_trace_reduce.py reads.

    python3 benchmarks/chip/tools/record_trace.py --out <dir>   # on a TPU

Serves two short requests through the paged engine, with the compiled
Pallas kernel, on a two-layer model at qwen3-0.6b's attention widths, with
the profiler on and a pause between the two engine runs (so the trace
holds an idle gap), and writes the profiler's .xplane.pb under <dir>.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    import jax
    import numpy as np

    import arch
    import harness
    from repro.serving import backends, scheduler

    if jax.devices()[0].platform != "tpu":
        print("record_trace.py: needs a TPU", file=sys.stderr)
        return 1
    c = harness._json(HERE / "configs" / "qwen3-0.6b.json")
    a = arch.of(c)
    layers = a.attention_layers(c)[:2]  # the first two layers only
    cfg = dataclasses.replace(a.model_config(c), num_layers=len(layers),
                              vocab_size=4096)
    qz = harness.quantizer(c["serving"]["kv_quant"], layers)
    params = harness.make_weights(0, cfg, a.init_leaf)
    sched = scheduler.SchedulerConfig(num_slots=2, page_size=16,
                                      num_pages=64, max_context=256,
                                      prefill_chunk=64, telemetry=False)
    eng = scheduler.PagedServingEngine(
        params, cfg, backends.QuantPallasBackend(cfg, qz, interpret=False),
        sched)
    rng = np.random.default_rng(0)
    reqs = [scheduler.Request(rid=i, tokens=rng.integers(0, 4096, 100 + i,
                                                         dtype=np.int32),
                              max_new_tokens=24) for i in range(2)]
    eng.run(reqs)  # compile outside the trace
    jax.profiler.start_trace(args.out)
    eng.run([dataclasses.replace(r, rid=r.rid + 10) for r in reqs])
    time.sleep(0.05)
    eng.run([dataclasses.replace(r, rid=r.rid + 20) for r in reqs])
    jax.profiler.stop_trace()
    print(f"trace under {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
