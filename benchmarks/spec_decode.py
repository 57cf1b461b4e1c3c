"""Speculative-decoding benchmark: draft-verify-rollback vs plain decode.

Replays a trace with repeated structure (templated prompts built from
recurring motifs — the shape of real templated/code traffic, and the case
prompt-lookup drafting exists for) through the paged continuous-batching
scheduler twice on the quant-pallas bitpack backend:

    plain        one forward pass per emitted token (burst decode)
    speculative  each dispatch scores the pending token + up to draft_len
                 prompt-lookup drafts, commits the accepted run, rolls the
                 rejected suffix back (serving/speculate.py)

Verifies the speculative run's greedy tokens are BITWISE identical to the
plain run's per request (losslessness is a gate, not a claim), and that
speculation is a WALL-CLOCK win, not just a step-count win. Emits
BENCH_spec.json and exits non-zero when

  * any request's tokens differ between the two runs, or
  * speculative tokens/sec < plain tokens/sec (speedup < 1.0).

Through PR 5 the gate was steps_per_token < 1.0 — the counter moved but
the clock was allowed not to. ISSUE 6's fused on-device spec burst
(draft -> verify -> accept -> commit in ONE dispatch per round, host
readback once per burst) plus AOT warmup is what makes the wall-clock
gate honest: both modes are measured post-warmup on the same engine
discipline, so the speedup is the dispatch math, not compile noise.
steps_per_token is still reported (it bounds the speedup on
bandwidth-bound hardware).

Usage:
    PYTHONPATH=src python benchmarks/spec_decode.py [--smoke] \
        [--interpret] [--out BENCH_spec.json]

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
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import mixedkv, rates
from repro.core.quantizer import KVQuantizer, QuantizerConfig
from repro.models import transformer
from repro.serving import backends as backends_lib
from repro.serving import pages as pages_lib
from repro.serving import scheduler as scheduler_lib

BENCH_CFG = ModelConfig(
    name="bench-spec", family="decoder", num_layers=2, d_model=256,
    num_heads=8, num_kv_heads=2, d_ff=512, vocab_size=128, head_dim=32,
)

FULL = dict(n_requests=24, motif_lo=4, motif_hi=8, reps_lo=3, reps_hi=6,
            tail_hi=8, budget_lo=16, budget_hi=48, num_slots=4,
            page_size=16, prefill_chunk=16, max_burst=16, draft_len=4,
            reps=3)
SMOKE = dict(n_requests=8, motif_lo=3, motif_hi=6, reps_lo=3, reps_hi=4,
             tail_hi=4, budget_lo=8, budget_hi=20, num_slots=4,
             page_size=16, prefill_chunk=16, max_burst=16, draft_len=4,
             reps=3)


def make_trace(p: dict, seed: int = 0) -> list[scheduler_lib.Request]:
    """Repeated-structure prompts: a short random motif tiled several
    times plus a short random tail — templated traffic in miniature. The
    tiling seeds the n-gram drafter from step one, and the (untrained)
    model's greedy continuations of such prompts are themselves highly
    periodic, which is exactly the regime speculation converts into
    multi-token steps. All requests arrive at t=0: this benchmark isolates
    decode scheduling, not admission."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(p["n_requests"]):
        motif = rng.integers(0, BENCH_CFG.vocab_size,
                             int(rng.integers(p["motif_lo"],
                                              p["motif_hi"] + 1)))
        tiles = int(rng.integers(p["reps_lo"], p["reps_hi"] + 1))
        tail = rng.integers(0, BENCH_CFG.vocab_size,
                            int(rng.integers(0, p["tail_hi"] + 1)))
        tokens = np.concatenate([np.tile(motif, tiles), tail])
        reqs.append(scheduler_lib.Request(
            rid=i, tokens=tokens.astype(np.int32),
            max_new_tokens=int(rng.integers(p["budget_lo"],
                                            p["budget_hi"] + 1))))
    return reqs


def _engine(params, backend, reqs, p, speculate: bool):
    chunk = p["prefill_chunk"]
    max_span = max(-(-len(r.tokens) // chunk) * chunk + r.max_new_tokens
                   for r in reqs)
    per_req_pages = pages_lib.pages_for_tokens(max_span, p["page_size"])
    sched = scheduler_lib.SchedulerConfig(
        num_slots=p["num_slots"], page_size=p["page_size"],
        num_pages=1 + per_req_pages * p["num_slots"] + 2,
        max_context=max_span, prefill_chunk=chunk,
        max_burst=p["max_burst"], speculate=speculate,
        draft_len=p["draft_len"])
    return scheduler_lib.PagedServingEngine(params, BENCH_CFG, backend,
                                            sched)


def run_modes(params, backend, reqs, p
              ) -> tuple[tuple[list[np.ndarray], dict],
                         tuple[list[np.ndarray], dict]]:
    """Timed plain + speculative replays, INTERLEAVED: plain rep i runs
    back-to-back with spec rep i, and each mode keeps its best-of-reps
    wall. On a shared/noisy host a mode-at-a-time schedule lets a load
    spike land entirely on one mode and swing the speedup ratio both
    ways; interleaving gives both modes the same shot at every quiet
    window, so best-of converges to the honest ratio."""
    engines = [_engine(params, backend, reqs, p, spec)
               for spec in (False, True)]
    for eng in engines:
        eng.warmup()  # AOT-compile every dispatch variant up front
        eng.run(reqs)  # warm run: data caches, allocator paths
    outs = [(None, None), (None, None)]
    for _ in range(p["reps"]):
        for i, eng in enumerate(engines):
            results, stats = eng.run(reqs)
            if outs[i][1] is None or stats["wall_s"] < outs[i][1]["wall_s"]:
                outs[i] = ([r.tokens for r in results], stats)
    return outs[0], outs[1]


def check(report: dict) -> list[str]:
    errs = []
    if not report.get("tokens_match"):
        errs.append("speculative greedy tokens differ from plain decode "
                    "on at least one request")
    speedup = report["summary"]["speedup_tokens_per_sec"]
    if speedup < 1.0:
        errs.append(
            f"speedup_tokens_per_sec {speedup:.3f} < 1.0: speculation is "
            f"not a wall-clock win on the repeated-structure trace (the "
            f"step-count savings are not reaching the clock)")
    return errs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny trace for CI")
    ap.add_argument("--out", type=Path, default=Path("BENCH_spec.json"))
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
    backend = backends_lib.QuantPallasBackend(BENCH_CFG, qz,
                                              interpret=args.interpret)
    reqs = make_trace(p, args.seed)

    t0 = time.perf_counter()
    ((plain_toks, plain_stats),
     (spec_toks, spec_stats)) = run_modes(params, backend, reqs, p)
    match = all((a.shape == b.shape) and bool((a == b).all())
                for a, b in zip(spec_toks, plain_toks))
    sp = spec_stats["spec"]

    report = {
        "meta": {
            "model": {k: getattr(BENCH_CFG, k) for k in
                      ("num_layers", "num_kv_heads", "head_dim", "d_model")},
            "schedule": "K128V64", "storage": "bitpack",
            "trace": {k: p[k] for k in p},
            "smoke": args.smoke,
            "backend": jax.default_backend(),
            "interpret": args.interpret,
            "bench_wall_s": time.perf_counter() - t0,
        },
        "tokens_match": match,
        "plain": plain_stats,
        "speculative": spec_stats,
        "summary": {
            "steps_per_token": sp["steps_per_token"],
            "acceptance_rate": sp["acceptance_rate"],
            "draft_accepted": sp["draft_accepted"],
            "draft_proposed": sp["draft_proposed"],
            # plain decode is 1.0 sequential pass per decode token by
            # construction, so the reduction is simply 1/steps_per_token
            "sequential_pass_reduction":
                sp["decode_tokens"] / max(sp["verify_steps"], 1),
            "speedup_tokens_per_sec":
                spec_stats["tokens_per_sec"]
                / max(plain_stats["tokens_per_sec"], 1e-9),
            # dispatch discipline: host round-trips per run and the AOT
            # variant accounting (post_warmup_variants must stay 0)
            "host_syncs_plain": plain_stats["perf"]["host_sync_count"],
            "host_syncs_spec": spec_stats["perf"]["host_sync_count"],
            "post_warmup_variants":
                plain_stats["perf"]["post_warmup_variants"]
                + spec_stats["perf"]["post_warmup_variants"],
        },
    }
    args.out.write_text(json.dumps(report, indent=2) + "\n")
    print(f"wrote {args.out}")
    # plain decode is exactly one sequential pass per decode token per
    # request (its decode_steps counter is batched dispatches, not
    # comparable to the per-request verify_steps sum)
    print(f"       plain: 1.000 steps/token by construction "
          f"({sp['decode_tokens']} decode tokens), "
          f"{plain_stats['tokens_per_sec']:8.1f} tok/s")
    print(f" speculative: {sp['verify_steps']} forward passes for "
          f"{sp['decode_tokens']} decode tokens "
          f"({sp['steps_per_token']:.3f} steps/token; "
          f"{sp['acceptance_rate']:.0%} of "
          f"{sp['draft_proposed']} drafts accepted), "
          f"{spec_stats['tokens_per_sec']:8.1f} tok/s")
    print(f"  tokens match: {match}; "
          f"{report['summary']['sequential_pass_reduction']:.2f}x fewer "
          f"sequential passes per token")
    print(f"  wall speedup: "
          f"{report['summary']['speedup_tokens_per_sec']:.2f}x tokens/sec; "
          f"host syncs (cumulative) plain="
          f"{report['summary']['host_syncs_plain']} spec="
          f"{report['summary']['host_syncs_spec']}; "
          f"post-warmup jit variants: "
          f"{report['summary']['post_warmup_variants']}")
    errs = check(report)
    for e in errs:
        print(f"CHECK FAILED: {e}", file=sys.stderr)
    return 1 if errs else 0


if __name__ == "__main__":
    raise SystemExit(main())
