"""Mixed-SLO soak benchmark: preemption under load + fault injection.

Replays a seeded Poisson trace of two SLO classes through the paged
serving engine on the pallas-bitpack backend:

    hogs         priority 0, long prompts, long generation budgets —
                 they occupy slots and pages for most of the trace. A
                 slice of them carries a tight admission deadline, so
                 overload produces explicit shedding, not queue growth.
    interactive  priority 1, short prompts, small budgets, arriving
                 steadily WHILE the hogs run — the class whose tail
                 latency the SLO machinery exists to protect.

Three runs over the same trace, all with per-tick conservation checks
(`debug_conservation`) and the wall-clock watchdog armed:

    baseline   preempt off: legacy FCFS admission. Interactive requests
               wait behind whichever hogs hold the slots.
    preempt    preempt on: interactive arrivals preempt hogs by spilling
               their packed pages to host memory; hogs restore and
               resume bitwise-losslessly when capacity frees.
    soak       preempt + tiered degradation (`DegradeConfig`) + a seeded
               adversarial fault campaign (`FaultInjector.random`):
               transient alloc failures, delayed/failed restores,
               temporary pool steals, and cancellations targeting hogs
               (including mid-verify-window cancels).

Every run's surviving tokens are compared against per-request static
references (`serving.engine.generate`, same kernel block size):
completed non-degraded requests must match BITWISE, cancelled requests
must be a bitwise prefix, shed requests must be empty. Emits
BENCH_soak.json and exits non-zero when

  * any run leaks pages (either tier) or trips a conservation check,
  * any run compiles a jit variant after warmup,
  * any surviving request's tokens violate the contract above, or
  * (full runs only) the preempt run's interactive p99 latency fails to
    beat the no-preemption baseline, or the soak run never actually
    exercised the pressure ladder (a trace with no spill and no tier-2
    degradation would have tested nothing).

Usage:
    PYTHONPATH=src python benchmarks/soak.py [--smoke] \
        [--interpret] [--out BENCH_soak.json]

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
from repro.serving.faults import FaultInjector

# same scale rationale as serve_throughput: scheduling is the subject,
# but decode compute must dominate python dispatch
BENCH_CFG = ModelConfig(
    name="bench-soak", family="decoder", num_layers=2, d_model=256,
    num_heads=8, num_kv_heads=2, d_ff=512, vocab_size=128, head_dim=32,
)

# hog budgets are sized against the ~150 tok/s-per-slot decode rate of
# this geometry on CPU: a hog must hold its slot for ~1s so interactive
# arrivals (slow Poisson clock) land WHILE hogs run — the preemption
# trigger. Hogs arrive fast (deep queue from t~0), so the baseline FCFS
# run shows the queueing tail the preempt run is gated to beat. The FULL
# hog prompt/budget ranges are chosen so every hog reserves the SAME
# page count (span 153..160 at page_size 8 -> 20 pages): two hogs fill
# the tier-1 pool of the degrade run exactly, a third never fits (a slot
# stays free), and an interactive's 3-page reservation exceeds the 2
# free pages — so pressure arrives as page-shortage-with-a-free-slot,
# the degrade rung's trigger, instead of always as a slot shortage.
FULL = dict(n_requests=24, hog_prompt_lo=32, hog_prompt_hi=32,
            hog_budget_lo=121, hog_budget_hi=128, int_prompt_lo=16,
            int_prompt_hi=16, int_budget_lo=5, int_budget_hi=8,
            hog_interarrival_s=0.02, int_interarrival_s=0.4,
            deadline_every=6, deadline_ms=40.0,
            num_slots=2, page_size=8, prefill_chunk=16, max_burst=8,
            soak_slots=3, degrade_pages=64, fault_events=10,
            fault_ticks=60, max_wall_s=900.0)
SMOKE = dict(n_requests=9, hog_prompt_lo=8, hog_prompt_hi=24,
             hog_budget_lo=20, hog_budget_hi=32, int_prompt_lo=4,
             int_prompt_hi=8, int_budget_lo=3, int_budget_hi=5,
             hog_interarrival_s=0.02, int_interarrival_s=0.12,
             deadline_every=6, deadline_ms=40.0,
             num_slots=2, page_size=8, prefill_chunk=16, max_burst=8,
             soak_slots=3, degrade_pages=64, fault_events=6,
             fault_ticks=40, max_wall_s=900.0)


def make_trace(p: dict, seed: int = 0) -> list[scheduler_lib.Request]:
    """Seeded two-class Poisson trace.

    Every third request is interactive (priority 1), arriving on a SLOW
    Poisson clock so it lands mid-hog — the preemption trigger. Hogs
    (priority 0) arrive fast and saturate the slots from t=0; every
    `deadline_every`-th hog carries a `deadline_ms` admission deadline it
    cannot meet under load, exercising the shed rung in every mode.
    """
    rng = np.random.default_rng(seed)
    reqs, t_hog, t_int = [], 0.0, 0.0
    for i in range(p["n_requests"]):
        if i % 3 == 2:  # interactive
            t_int += float(rng.exponential(p["int_interarrival_s"]))
            plen = int(rng.integers(p["int_prompt_lo"],
                                    p["int_prompt_hi"] + 1))
            budget = int(rng.integers(p["int_budget_lo"],
                                      p["int_budget_hi"] + 1))
            reqs.append(scheduler_lib.Request(
                rid=i, tokens=rng.integers(0, BENCH_CFG.vocab_size, plen
                                           ).astype(np.int32),
                max_new_tokens=budget, arrival=t_int, priority=1))
        else:  # hog
            t_hog += float(rng.exponential(p["hog_interarrival_s"]))
            plen = int(rng.integers(p["hog_prompt_lo"],
                                    p["hog_prompt_hi"] + 1))
            budget = int(rng.integers(p["hog_budget_lo"],
                                      p["hog_budget_hi"] + 1))
            deadline = (p["deadline_ms"]
                        if i and i % p["deadline_every"] == 0 else None)
            reqs.append(scheduler_lib.Request(
                rid=i, tokens=rng.integers(0, BENCH_CFG.vocab_size, plen
                                           ).astype(np.int32),
                max_new_tokens=budget, arrival=t_hog, priority=0,
                deadline_ms=deadline))
    return reqs


def static_refs(params, backend, reqs) -> dict:
    """Per-request greedy reference tokens from the static engine, one
    padded batch (same kernel block size -> bitwise-comparable)."""
    lens = [len(r.tokens) for r in reqs]
    batch = np.zeros((len(reqs), max(lens)), np.int32)
    for i, r in enumerate(reqs):
        batch[i, :lens[i]] = r.tokens
    res = engine_lib.generate(
        params, BENCH_CFG, backend, jnp.asarray(batch),
        jnp.asarray(lens, jnp.int32),
        max_new_tokens=max(r.max_new_tokens for r in reqs))
    toks = np.asarray(res.tokens)
    return {r.rid: toks[i, :r.max_new_tokens] for i, r in enumerate(reqs)}


def make_engine(params, backend, p: dict, *, preempt: bool,
                degrade: bool, num_slots: int):
    chunk = p["prefill_chunk"]
    max_span = (-(-p["hog_prompt_hi"] // chunk) * chunk
                + p["hog_budget_hi"])
    per_req_pages = pages_lib.pages_for_tokens(max_span, p["page_size"])
    if degrade:
        # one slot more than tier-1 page capacity: pressure arrives as a
        # page shortage WITH a free slot, the degrade rung's trigger
        num_pages = 1 + per_req_pages * (num_slots - 1) + 2
    else:
        num_pages = 1 + per_req_pages * num_slots + 2
    sched = scheduler_lib.SchedulerConfig(
        num_slots=num_slots, page_size=p["page_size"],
        num_pages=num_pages, max_context=max_span, prefill_chunk=chunk,
        max_burst=p["max_burst"], preempt=preempt,
        degrade=(scheduler_lib.DegradeConfig(num_pages=p["degrade_pages"])
                 if degrade else None),
        debug_conservation=True, max_wall_s=p["max_wall_s"])
    eng = scheduler_lib.PagedServingEngine(params, BENCH_CFG, backend,
                                           sched)
    eng.warmup()
    return eng


def check_tokens(results, refs) -> list[str]:
    """The survival contract: completed non-degraded requests match the
    static reference BITWISE, cancelled ones are a bitwise prefix, shed
    ones are empty. Degraded requests are lossy by design — excluded."""
    errs = []
    for r in results:
        ref, toks = refs[r.rid], np.asarray(r.tokens)
        if r.status == "shed":
            if len(toks):
                errs.append(f"rid {r.rid}: shed with {len(toks)} tokens")
        elif getattr(r, "degraded", False):
            continue
        elif r.status == "completed":
            if toks.shape != ref.shape or not bool((toks == ref).all()):
                errs.append(f"rid {r.rid}: completed tokens != static ref")
        elif r.status == "cancelled":
            if not bool((toks == ref[:len(toks)]).all()):
                errs.append(f"rid {r.rid}: cancelled tokens not a prefix "
                            f"of static ref")
    return errs


def run_one(eng, reqs, refs, faults_seed=None, fault_p=None) -> dict:
    """Warm replay (spill/restore/migrate eager ops compile here), then
    the measured replay. Fresh injector per replay — campaigns are
    tick-deterministic, not shared-state."""
    def mk_faults():
        if faults_seed is None:
            return None
        lo = [r.rid for r in reqs if r.priority == 0]
        return FaultInjector.random(
            faults_seed, fault_p["fault_ticks"], rids=lo,
            n_events=fault_p["fault_events"])

    eng.run(list(reqs), faults=mk_faults())  # warm data/eager-op caches
    results, stats = eng.run(list(reqs), faults=mk_faults())
    sched = eng.sched
    leaked = (sched.num_pages - 1) - eng.allocator.num_free
    leaked2 = 0
    if eng.allocator2 is not None:
        leaked2 = ((sched.degrade.num_pages - 1)
                   - eng.allocator2.num_free)
    statuses = {s: sum(1 for r in results if r.status == s)
                for s in scheduler_lib.RESULT_STATUSES}
    return {
        "wall_s": stats["wall_s"],
        "slo": stats["slo"],
        "faults": stats.get("faults"),
        "perf": {"post_warmup_variants":
                 stats["perf"]["post_warmup_variants"],
                 "jit_variants_compiled":
                 stats["perf"]["jit_variants_compiled"]},
        "statuses": statuses,
        "leaked_pages": int(leaked),
        "leaked_pages_tier2": int(leaked2),
        "token_errors": check_tokens(results, refs),
    }


def check(report: dict, smoke: bool) -> list[str]:
    errs = []
    for name in ("baseline", "preempt", "soak"):
        run = report[name]
        if run["leaked_pages"] or run["leaked_pages_tier2"]:
            errs.append(f"{name}: leaked {run['leaked_pages']} tier-1 / "
                        f"{run['leaked_pages_tier2']} tier-2 pages")
        if run["perf"]["post_warmup_variants"]:
            errs.append(f"{name}: {run['perf']['post_warmup_variants']} "
                        f"jit variants compiled after warmup")
        for e in run["token_errors"]:
            errs.append(f"{name}: {e}")
    if not smoke:
        s = report["summary"]
        if s["interactive_p99_preempt_s"] >= s["interactive_p99_baseline_s"]:
            errs.append(
                f"preemption did not improve interactive p99: "
                f"{s['interactive_p99_preempt_s']:.3f}s vs baseline "
                f"{s['interactive_p99_baseline_s']:.3f}s")
        if report["soak"]["slo"]["spills"] < 1:
            errs.append("soak run never spilled — the trace exercised "
                        "no preemption pressure")
        if report["soak"]["slo"]["degraded"] < 1:
            errs.append("soak run never degraded a victim — the trace "
                        "exercised no tier-2 pressure")
    return errs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny trace for CI")
    ap.add_argument("--out", type=Path, default=Path("BENCH_soak.json"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", type=Path, default=None,
                    help="write the soak run's telemetry ring buffer as "
                         "Chrome/Perfetto trace_event JSON — the "
                         "replayable timeline that ships with BENCH_soak "
                         "(open at https://ui.perfetto.dev)")
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
    refs = static_refs(params, backend, reqs)

    runs = {}
    for name, kw, fs in (
            ("baseline", dict(preempt=False, degrade=False,
                              num_slots=p["num_slots"]), None),
            ("preempt", dict(preempt=True, degrade=False,
                             num_slots=p["num_slots"]), None),
            ("soak", dict(preempt=True, degrade=True,
                          num_slots=p["soak_slots"]), args.seed + 1)):
        eng = make_engine(params, backend, p, **kw)
        runs[name] = run_one(eng, reqs, refs, faults_seed=fs, fault_p=p)
        if name == "soak" and args.trace_out is not None:
            args.trace_out.write_text(
                eng.telemetry.tracer.to_perfetto_json() + "\n")
            print(f"wrote {args.trace_out} "
                  f"({len(eng.telemetry.tracer.events())} trace events)")
        del eng

    def p99(run):
        cl = run["slo"]["per_class"].get("1")
        return cl["latency_p99_s"] if cl else float("inf")

    report = {
        "meta": {
            "model": {k: getattr(BENCH_CFG, k) for k in
                      ("num_layers", "num_kv_heads", "head_dim",
                       "d_model")},
            "schedule": "K128V64", "storage": "bitpack",
            "trace": {k: p[k] for k in p},
            "smoke": args.smoke,
            "backend": jax.default_backend(),
            "interpret": args.interpret,
        },
        **runs,
        "summary": {
            "interactive_p99_baseline_s": p99(runs["baseline"]),
            "interactive_p99_preempt_s": p99(runs["preempt"]),
            "interactive_p99_soak_s": p99(runs["soak"]),
            "soak_spills": runs["soak"]["slo"]["spills"],
            "soak_restores": runs["soak"]["slo"]["restores"],
            "soak_degraded": runs["soak"]["slo"]["degraded"],
            "soak_faults_delivered":
                (runs["soak"]["faults"] or {}).get("delivered", 0),
            "leaked_pages_total": sum(
                r["leaked_pages"] + r["leaked_pages_tier2"]
                for r in runs.values()),
            "tokens_match": all(not r["token_errors"]
                                for r in runs.values()),
        },
    }
    args.out.write_text(json.dumps(report, indent=2) + "\n")
    print(f"wrote {args.out}")
    for name in ("baseline", "preempt", "soak"):
        r = runs[name]
        slo = r["slo"]
        print(f"  {name:>8}: wall {r['wall_s']:6.2f}s  "
              f"done {slo['completed']:2d}  shed {slo['shed']}  "
              f"cancel {slo['cancelled']}  spill {slo['spills']}  "
              f"restore {slo['restores']}  degrade {slo['degraded']}  "
              f"leak {r['leaked_pages']}+{r['leaked_pages_tier2']}  "
              f"post-warm variants {r['perf']['post_warmup_variants']}")
    s = report["summary"]
    print(f"  interactive p99: baseline "
          f"{s['interactive_p99_baseline_s']:.3f}s -> preempt "
          f"{s['interactive_p99_preempt_s']:.3f}s; tokens_match "
          f"{s['tokens_match']}")
    errs = check(report, args.smoke)
    for e in errs:
        print(f"CHECK FAILED: {e}", file=sys.stderr)
    return 1 if errs else 0


if __name__ == "__main__":
    raise SystemExit(main())
