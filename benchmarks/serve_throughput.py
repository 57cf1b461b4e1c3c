"""Serving-throughput benchmark: continuous batching vs the static engine.

Replays a Poisson request trace (mixed prompt lengths, long-tailed
generation budgets) through both serving paths on the paged pallas-bitpack
backend at EQUAL slot capacity:

    static      consecutive arrival-order batches of `num_slots` requests
                through `serving.engine.generate`, each batch run to
                completion — every request pays for its batch's longest
                prompt (padding) and longest budget (decode steps), and the
                next batch waits for the whole previous one to drain. This
                is the dense-cache baseline at the same memory/slot budget;
                it gets the best case of all requests present at t=0 and
                the same kernel block size as the paged engine
                (block_t = page_size), so the comparison isolates
                *scheduling*, not kernel granularity. One cost differs:
                the contiguous attend (`kernels.qattn.qattn`) copies each
                layer's cache into page layout on every call, which the
                paged engine does not; until that copy is measured on a
                chip, a device speedup over this baseline overstates what
                scheduling alone buys.
    continuous  `serving.scheduler.PagedServingEngine` — requests admitted
                into decode slots on arrival, chunked prefill, burst
                decoding, eviction on budget with pages freed immediately.

Reports aggregate tokens/sec and per-request p50/p99 latency for both, and
verifies the continuous engine's greedy tokens are identical per request to
the static engine's (truncated to each request's budget). Emits
BENCH_serve.json and exits non-zero when

  * any request's tokens differ between the engines, or
  * continuous-batching tokens/sec < static-batch tokens/sec on the trace.

Usage:
    PYTHONPATH=src python benchmarks/serve_throughput.py [--smoke] \
        [--interpret] [--out BENCH_serve.json]

--interpret runs the Pallas kernels in the interpreter, the way to run on
a CPU; its walls are not device timings, and the script refuses it on a
TPU. Without it the kernels are compiled, which needs a TPU. The report's
meta records both `backend` and `interpret`.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import mixedkv, rates
from repro.core.quantizer import KVQuantizer, QuantizerConfig
from repro.models import transformer
from repro.serving import backends as backends_lib
from repro.serving import engine as engine_lib
from repro.serving import pages as pages_lib
from repro.serving import scheduler as scheduler_lib

# one small decoder: serving throughput is about scheduling, not model
# scale — but big enough that a decode step's compute dominates dispatch
# overhead (d_model 128 / d_ff 256), else the comparison measures the
# python control plane instead of the schedule
BENCH_CFG = ModelConfig(
    name="bench-serve", family="decoder", num_layers=2, d_model=256,
    num_heads=8, num_kv_heads=2, d_ff=512, vocab_size=128, head_dim=32,
)

FULL = dict(n_requests=32, prompt_lo=8, prompt_hi=48, budget_lo=2,
            budget_mid=12, budget_hi=64, mean_interarrival_s=0.002,
            num_slots=4, page_size=16, prefill_chunk=16, max_burst=16,
            reps=3)
SMOKE = dict(n_requests=12, prompt_lo=4, prompt_hi=24, budget_lo=2,
             budget_mid=6, budget_hi=32, mean_interarrival_s=0.001,
             num_slots=4, page_size=8, prefill_chunk=16, max_burst=16,
             reps=3)


def make_trace(p: dict, seed: int = 0) -> list[scheduler_lib.Request]:
    """Poisson arrivals, mixed prompt lengths, long-tailed budgets (seeded).

    The budget mix is the production shape: mostly short answers plus a
    steady stream of long generations (every `num_slots`-th request) —
    with arrival-order batching every static batch therefore carries
    exactly one straggler, the canonical capacity-stranding pattern the
    continuous scheduler exists to fix. Random tail placement only changes
    WHICH batches strand (several stragglers landing in one batch lets the
    static engine amortize them); the stratified pattern makes the gated
    comparison deterministic in trace composition.
    """
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(p["mean_interarrival_s"],
                                         p["n_requests"]))
    reqs = []
    for i in range(p["n_requests"]):
        plen = int(rng.integers(p["prompt_lo"], p["prompt_hi"] + 1))
        if i % p["num_slots"] == p["num_slots"] - 1:
            budget = int(rng.integers(p["budget_mid"], p["budget_hi"] + 1))
        else:
            budget = int(rng.integers(p["budget_lo"], p["budget_mid"] + 1))
        reqs.append(scheduler_lib.Request(
            rid=i,
            tokens=rng.integers(0, BENCH_CFG.vocab_size, plen
                                ).astype(np.int32),
            max_new_tokens=budget,
            arrival=float(arrivals[i]),
        ))
    return reqs


def run_static(params, backend, reqs, num_slots: int, reps: int
               ) -> tuple[list[np.ndarray], dict]:
    """Arrival-order batches of `num_slots`, each run to completion.

    A batch cannot start before its last request has arrived (static
    batching fills a batch, then runs it) nor before the previous batch
    drained. Wall-clocked after a warmup pass over the same batch shapes
    (compile time is not a scheduling property); best of `reps` timed
    passes, since shared CI runners are noisy. A request's latency is the
    time from ITS arrival until ITS batch finishes.
    """
    batches = [reqs[i:i + num_slots] for i in range(0, len(reqs), num_slots)]

    def make_inputs(chunk):
        lens = [len(r.tokens) for r in chunk]
        s_max = max(lens)
        batch = np.zeros((len(chunk), s_max), np.int32)
        for i, r in enumerate(chunk):
            batch[i, :lens[i]] = r.tokens
        return (jnp.asarray(batch), jnp.asarray(lens, jnp.int32),
                max(r.max_new_tokens for r in chunk))

    inputs = [make_inputs(c) for c in batches]
    for prompts, plens, gen_max in inputs:  # warmup / compile
        jax.block_until_ready(engine_lib.generate(
            params, BENCH_CFG, backend, prompts, plens,
            max_new_tokens=gen_max).tokens)

    best = None
    per_req: list[np.ndarray] = []
    for _ in range(reps):
        per_req = []
        batch_done_at = []
        steps = token_steps = 0
        t0 = time.perf_counter()
        for chunk, (prompts, plens, gen_max) in zip(batches, inputs):
            gate = max(r.arrival for r in chunk)  # wait for batch to fill
            now = time.perf_counter() - t0
            if now < gate:
                time.sleep(gate - now)
            res = engine_lib.generate(params, BENCH_CFG, backend, prompts,
                                      plens, max_new_tokens=gen_max)
            jax.block_until_ready(res.tokens)
            batch_done_at.append(time.perf_counter() - t0)
            toks = np.asarray(res.tokens)
            per_req.extend(toks[i, :r.max_new_tokens]
                           for i, r in enumerate(chunk))
            steps += int(res.steps)
            token_steps += int(res.steps) * len(chunk)
        wall = time.perf_counter() - t0
        if best is not None and wall >= best["wall_s"]:
            continue
        useful = int(sum(r.max_new_tokens for r in reqs))
        lat = np.concatenate([
            np.asarray([batch_done_at[j] - r.arrival for r in c])
            for j, c in enumerate(batches)])
        best = {
            "wall_s": wall,
            "new_tokens": useful,
            "tokens_per_sec": useful / max(wall, 1e-9),
            "latency_p50_s": float(np.percentile(lat, 50)),
            "latency_p99_s": float(np.percentile(lat, 99)),
            "decode_steps": steps,
            "token_steps_computed": token_steps,
            "num_batches": len(batches),
        }
    return per_req, best


def run_continuous(params, backend, reqs, p: dict
                   ) -> tuple[list[np.ndarray], dict]:
    chunk = p["prefill_chunk"]
    max_span = max(-(-len(r.tokens) // chunk) * chunk + r.max_new_tokens
                   for r in reqs)
    per_req_pages = pages_lib.pages_for_tokens(max_span, p["page_size"])
    sched = scheduler_lib.SchedulerConfig(
        num_slots=p["num_slots"], page_size=p["page_size"],
        num_pages=1 + per_req_pages * p["num_slots"] + 2,
        max_context=max_span, prefill_chunk=chunk,
        max_burst=p["max_burst"])
    eng = scheduler_lib.PagedServingEngine(params, BENCH_CFG, backend, sched)
    # AOT warmup (compiles every prefill bucket + decode-burst width up
    # front — serving/compile_cache.py) plus one warm replay for data
    # caches, then best of `reps` timed replays (greedy tokens are
    # identical across reps; only the wall clock varies with CI noise)
    eng.warmup()
    eng.run([scheduler_lib.Request(r.rid, r.tokens, r.max_new_tokens, 0.0)
             for r in reqs])
    per_req, best = [], None
    for _ in range(p["reps"]):
        results, stats = eng.run(reqs)
        if best is None or stats["wall_s"] < best["wall_s"]:
            per_req = [r.tokens for r in results]
            best = stats
    best["token_steps_computed"] = best["decode_steps"] * p["num_slots"]
    return per_req, best


def check(report: dict) -> list[str]:
    errs = []
    if not report.get("tokens_match"):
        errs.append("continuous-batching tokens differ from the static "
                    "engine on at least one request")
    cont = report["continuous"]["tokens_per_sec"]
    stat = report["static"]["tokens_per_sec"]
    if cont < stat:
        errs.append(
            f"continuous-batching tokens/sec {cont:.2f} below the "
            f"static-batch engine {stat:.2f} on a mixed-length trace")
    return errs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny trace for CI")
    ap.add_argument("--out", type=Path, default=Path("BENCH_serve.json"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--interpret", action="store_true",
                    help="run the Pallas kernels in the interpreter (CPU); "
                         "without it they are compiled, which needs a TPU")
    args = ap.parse_args(argv)
    if args.interpret and jax.default_backend() == "tpu":
        ap.error("--interpret on a TPU would time the Pallas interpreter "
                 "and label it tpu; drop --interpret")
    p = SMOKE if args.smoke else FULL

    params, _ = transformer.init_params(jax.random.PRNGKey(0), BENCH_CFG)
    qz = KVQuantizer(QuantizerConfig(
        head_dim=BENCH_CFG.head_dim,
        schedule=mixedkv.uniform(BENCH_CFG.num_layers),
        k_norm=rates.NORM_K8, v_norm=rates.NORM_V4_LOG, storage="bitpack"))
    # block_t = page_size gives the static baseline the SAME kernel block
    # granularity as the paged engine (and makes the token comparison
    # bit-for-bit: identical online-softmax accumulation order)
    backend = backends_lib.QuantPallasBackend(
        BENCH_CFG, qz, interpret=args.interpret, block_t=p["page_size"])
    reqs = make_trace(p, args.seed)

    static_toks, static_stats = run_static(params, backend, reqs,
                                           p["num_slots"], p["reps"])
    cont_toks, cont_stats = run_continuous(params, backend, reqs, p)
    match = all((a.shape == b.shape) and bool((a == b).all())
                for a, b in zip(cont_toks, static_toks))

    report = {
        "meta": {
            "model": {k: getattr(BENCH_CFG, k) for k in
                      ("num_layers", "num_kv_heads", "head_dim", "d_model")},
            "schedule": "K128V64", "storage": "bitpack",
            "trace": {k: p[k] for k in p},
            "smoke": args.smoke,
            "backend": jax.default_backend(),
            "interpret": args.interpret,
        },
        "tokens_match": match,
        "static": static_stats,
        "continuous": cont_stats,
        "summary": {
            "speedup_tokens_per_sec":
                cont_stats["tokens_per_sec"]
                / max(static_stats["tokens_per_sec"], 1e-9),
            "static_token_steps": static_stats["token_steps_computed"],
            "continuous_token_steps": cont_stats["token_steps_computed"],
        },
    }
    args.out.write_text(json.dumps(report, indent=2) + "\n")
    print(f"wrote {args.out}")
    for name, s in (("static", static_stats), ("continuous", cont_stats)):
        print(f"  {name:>10}: {s['tokens_per_sec']:8.1f} tok/s  "
              f"p50 {s['latency_p50_s'] * 1e3:8.1f} ms  "
              f"p99 {s['latency_p99_s'] * 1e3:8.1f} ms  "
              f"({s['decode_steps']} decode steps)")
    print(f"  tokens match: {match}; speedup "
          f"{report['summary']['speedup_tokens_per_sec']:.2f}x")
    errs = check(report)
    for e in errs:
        print(f"CHECK FAILED: {e}", file=sys.stderr)
    return 1 if errs else 0


if __name__ == "__main__":
    raise SystemExit(main())
