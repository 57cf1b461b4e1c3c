"""Prefix-reuse benchmark: copy-on-write prefix caching vs cold prefill.

Real traffic is dominated by shared prefixes (system prompts, few-shot
templates). This harness replays a trace of `n_requests` prompts that all
begin with the same `shared_prefix`-token system prompt (plus a short
per-request suffix) through the paged continuous-batching engine twice:

    cold    `prefix_cache="cold"` — every request prefills its whole
            prompt. Same prefill numerics as sharing (requantized-prefix
            chunked prefill), just no trie, which makes it the bitwise
            parity baseline: identical greedy tokens are a *gate*, not a
            hope.
    shared  `prefix_cache="share"` — the system prompt's packed pages are
            prefilled once, then every later request maps them by
            reference (refcount++) and prefills only its own suffix.

Both engines are warmed (compile + trie population) before timing; walls
are best-of-`reps`. The interesting numbers are the prefill work counters,
which are deterministic: `prefill_tokens_computed` drops by a factor of
~(S + suffix) / suffix and `prefill_chunks` (device work dispatched, chunk
granularity) drops with it.

Emits BENCH_prefix.json and exits non-zero when

  * any request's greedy tokens differ between the two runs, or
  * the shared run's prefill chunk count is not strictly below cold, or
  * (full mode) the shared run's prefill wall is not strictly below cold.

Usage:
    PYTHONPATH=src python benchmarks/prefix_reuse.py [--smoke] \
        [--interpret] [--out BENCH_prefix.json]

--interpret runs the Pallas kernels in the interpreter, the way to run on
a CPU; its walls are not device timings, and the script refuses it on a
TPU. Without it the kernels are compiled, which needs a TPU. The report's
meta records both `backend` and `interpret`.
"""
from __future__ import annotations

import argparse
import json
import sys
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

# same small decoder as serve_throughput: prefix caching is a scheduling /
# memory property, not a model-scale one, but the model must be big enough
# that prefill compute (the thing sharing removes) dominates dispatch
BENCH_CFG = ModelConfig(
    name="bench-prefix", family="decoder", num_layers=2, d_model=256,
    num_heads=8, num_kv_heads=2, d_ff=512, vocab_size=128, head_dim=32,
)

FULL = dict(n_requests=32, shared_prefix=1024, suffix_lo=8, suffix_hi=24,
            budget_lo=2, budget_hi=6, num_slots=4, page_size=16,
            prefill_chunk=64, max_burst=8, reps=3)
SMOKE = dict(n_requests=8, shared_prefix=32, suffix_lo=4, suffix_hi=12,
             budget_lo=2, budget_hi=4, num_slots=2, page_size=8,
             prefill_chunk=16, max_burst=8, reps=2)


def make_trace(p: dict, seed: int = 0) -> list[scheduler_lib.Request]:
    """All requests share an S-token system prompt + a unique suffix;
    everything queued at t=0 (this benchmark isolates prefill work, not
    arrival scheduling — serve_throughput.py covers that)."""
    rng = np.random.default_rng(seed)
    system = rng.integers(0, BENCH_CFG.vocab_size,
                          p["shared_prefix"]).astype(np.int32)
    reqs = []
    for i in range(p["n_requests"]):
        sfx = rng.integers(
            0, BENCH_CFG.vocab_size,
            int(rng.integers(p["suffix_lo"], p["suffix_hi"] + 1))
        ).astype(np.int32)
        reqs.append(scheduler_lib.Request(
            rid=i, tokens=np.concatenate([system, sfx]),
            max_new_tokens=int(rng.integers(p["budget_lo"],
                                            p["budget_hi"] + 1))))
    return reqs


def build_engine(p: dict, params, backend, mode: str
                 ) -> scheduler_lib.PagedServingEngine:
    chunk = p["prefill_chunk"]
    max_span = max(-(-len(r.tokens) // chunk) * chunk + r.max_new_tokens
                   for r in make_trace(p))
    per_req = pages_lib.pages_for_tokens(max_span, p["page_size"])
    prefix_pages = pages_lib.pages_for_tokens(p["shared_prefix"],
                                              p["page_size"]) + 4
    sched = scheduler_lib.SchedulerConfig(
        num_slots=p["num_slots"], page_size=p["page_size"],
        num_pages=1 + per_req * p["num_slots"] + prefix_pages + 2,
        max_context=max_span, prefill_chunk=chunk,
        max_burst=p["max_burst"], prefix_cache=mode,
        prefix_pages=prefix_pages)
    return scheduler_lib.PagedServingEngine(params, BENCH_CFG, backend,
                                            sched)


def run_mode(p: dict, params, backend, reqs, mode: str
             ) -> tuple[list[np.ndarray], dict]:
    """Warm (compile; populate the trie in share mode), then best-of-reps
    timed replays. Greedy tokens are identical across reps by design."""
    eng = build_engine(p, params, backend, mode)
    eng.run(reqs)  # warmup
    per_req, best = [], None
    for _ in range(p["reps"]):
        results, stats = eng.run(reqs)
        if best is None or stats["wall_s"] < best["wall_s"]:
            per_req = [r.tokens for r in results]
            best = stats
    eng.allocator.check_conservation()
    return per_req, best


def check(report: dict, smoke: bool) -> list[str]:
    errs = []
    if not report.get("tokens_match"):
        errs.append("shared-prefix greedy tokens differ from the "
                    "no-sharing path on at least one request")
    cold_c = report["cold"]["prefill_chunks"]
    shared_c = report["shared"]["prefill_chunks"]
    if not shared_c < cold_c:
        errs.append(f"shared prefill chunk count {shared_c} not strictly "
                    f"below cold {cold_c}")
    if not smoke:
        cold_w = report["cold"]["prefill_wall_s"]
        shared_w = report["shared"]["prefill_wall_s"]
        if not shared_w < cold_w:
            errs.append(f"shared prefill wall {shared_w:.3f}s not "
                        f"strictly below cold {cold_w:.3f}s")
    return errs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny trace for CI")
    ap.add_argument("--out", type=Path, default=Path("BENCH_prefix.json"))
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
    backend = backends_lib.QuantPallasBackend(
        BENCH_CFG, qz, interpret=args.interpret, block_t=p["page_size"])
    reqs = make_trace(p, args.seed)

    cold_toks, cold_stats = run_mode(p, params, backend, reqs, "cold")
    shared_toks, shared_stats = run_mode(p, params, backend, reqs, "share")
    match = all((a.shape == b.shape) and bool((a == b).all())
                for a, b in zip(shared_toks, cold_toks))

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
        "cold": cold_stats,
        "shared": shared_stats,
        "summary": {
            "prefill_tokens_cold": cold_stats["prefill_tokens_computed"],
            "prefill_tokens_shared":
                shared_stats["prefill_tokens_computed"],
            "prefill_token_reduction":
                cold_stats["prefill_tokens_computed"]
                / max(shared_stats["prefill_tokens_computed"], 1),
            "prefill_chunk_reduction":
                cold_stats["prefill_chunks"]
                / max(shared_stats["prefill_chunks"], 1),
            "prefill_wall_speedup":
                cold_stats["prefill_wall_s"]
                / max(shared_stats["prefill_wall_s"], 1e-9),
            "wall_speedup":
                cold_stats["wall_s"] / max(shared_stats["wall_s"], 1e-9),
            "prefix_hit_tokens": shared_stats["prefix"]["hit_tokens"],
        },
    }
    args.out.write_text(json.dumps(report, indent=2) + "\n")
    print(f"wrote {args.out}")
    for name, s in (("cold", cold_stats), ("shared", shared_stats)):
        print(f"  {name:>7}: prefill {s['prefill_tokens_computed']:6d} tok "
              f"/ {s['prefill_chunks']:4d} chunks in "
              f"{s['prefill_wall_s'] * 1e3:8.1f} ms; total wall "
              f"{s['wall_s'] * 1e3:8.1f} ms")
    sm = report["summary"]
    print(f"  tokens match: {match}; prefill work "
          f"{sm['prefill_token_reduction']:.1f}x fewer tokens, "
          f"{sm['prefill_chunk_reduction']:.1f}x fewer chunks, wall "
          f"{sm['prefill_wall_speedup']:.1f}x")
    errs = check(report, args.smoke)
    for e in errs:
        print(f"CHECK FAILED: {e}", file=sys.stderr)
    return 1 if errs else 0


if __name__ == "__main__":
    raise SystemExit(main())
