"""Sharded-serving scaling: per-device pool HBM shrinks ~1/N, tokens exact.

A CPU rehearsal tool: it forces the CPU platform and
--xla_force_host_platform_device_count=8 before importing jax and runs
the Pallas kernels in the interpreter, so it runs anywhere and never
touches an accelerator. (The mesh path on real chips is run by
`python chip_smoke.py --chips 4`.) Serves one trace through
`serving.scheduler.PagedServingEngine` at mesh sizes {1, 2, 4} on the
simulated host mesh and reports, per mesh size:

  * bitwise token parity against the mesh=None single-device engine —
    THE sharding contract; `tokens_match` gates,
  * per-device page-pool bytes, measured from the committed arrays'
    `addressable_shards` (what each device actually holds, not a model):
    the kv-head split must put ~1/N of the pool on each device, with
    only sub-percent slack from indivisible packed trailing dims,
  * wall-clock + dispatch counts (informational on CPU: collective
    overhead at toy scale says nothing about real chips).

Headline summary (gated by tools/bench_diff.py against the committed
BENCH_shard.json in the CI shard-smoke job):

  tokens_match                 must hold
  ratios.per_device_bytes_n2   ~0.5   (lower is better)
  ratios.per_device_bytes_n4   ~0.25

Both ratios are shape-invariants of the pool split, so smoke and full
runs gate against the same committed baseline.

Usage:
    PYTHONPATH=src python benchmarks/shard_scaling.py [--smoke] \
        [--out BENCH_shard.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import ModelConfig  # noqa: E402
from repro.core import mixedkv, rates  # noqa: E402
from repro.core.quantizer import KVQuantizer, QuantizerConfig  # noqa: E402
from repro.launch import mesh as mesh_lib  # noqa: E402
from repro.models import transformer  # noqa: E402
from repro.serving import backends as backends_lib  # noqa: E402
from repro.serving import scheduler as scheduler_lib  # noqa: E402

BENCH_CFG = ModelConfig(
    name="bench-shard", family="decoder", num_layers=2, d_model=64,
    num_heads=8, num_kv_heads=8, d_ff=64, vocab_size=128, head_dim=8,
)

FULL = dict(n_requests=8, prompt_lo=5, prompt_hi=30, budget=8,
            num_slots=2, page_size=8, num_pages=64, prefill_chunk=8,
            max_burst=4)
SMOKE = dict(n_requests=4, prompt_lo=5, prompt_hi=30, budget=6,
             num_slots=2, page_size=8, num_pages=64, prefill_chunk=8,
             max_burst=4)

MESH_SIZES = (1, 2, 4)


def make_trace(p: dict, seed: int) -> list:
    rng = np.random.default_rng(seed)
    return [scheduler_lib.Request(
        rid=i,
        tokens=rng.integers(1, BENCH_CFG.vocab_size - 1,
                            size=int(rng.integers(p["prompt_lo"],
                                                  p["prompt_hi"] + 1))
                            ).astype(np.int32),
        max_new_tokens=p["budget"], arrival=0.0)
        for i in range(p["n_requests"])]


def per_device_pool_bytes(pool) -> int:
    """Max over devices of the pool bytes that device actually holds."""
    per_dev: dict = {}
    for leaf in jax.tree.leaves(pool):
        for s in leaf.addressable_shards:
            per_dev[s.device] = per_dev.get(s.device, 0) + s.data.nbytes
    return max(per_dev.values())


def serve(params, backend, reqs, p: dict, mesh) -> dict:
    sc = scheduler_lib.SchedulerConfig(
        num_slots=p["num_slots"], page_size=p["page_size"],
        num_pages=p["num_pages"], max_context=64,
        prefill_chunk=p["prefill_chunk"], max_burst=p["max_burst"],
        debug_conservation=True, mesh=mesh)
    eng = scheduler_lib.PagedServingEngine(params, BENCH_CFG, backend, sc)
    t0 = time.perf_counter()
    eng.warmup()
    warm = time.perf_counter() - t0
    results, stats = eng.run(reqs)
    eng.allocator.check_conservation()
    return {
        "tokens": {str(r.rid): [int(t) for t in r.tokens] for r in results},
        "per_device_pool_bytes": per_device_pool_bytes(eng.pool),
        "total_pool_bytes": int(stats["pool_bytes"]),
        "wall_s": stats["wall_s"],
        "warmup_s": warm,
        "tokens_per_sec": stats["tokens_per_sec"],
        "decode_steps": stats["decode_steps"],
        "prefill_chunks": stats["prefill_chunks"],
        "post_warmup_variants": stats["perf"]["post_warmup_variants"],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="tiny trace for CI")
    ap.add_argument("--out", type=Path, default=Path("BENCH_shard.json"))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    p = SMOKE if args.smoke else FULL

    if len(jax.devices()) < max(MESH_SIZES):
        print(f"FATAL: need {max(MESH_SIZES)} simulated devices, have "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2

    qz = KVQuantizer(QuantizerConfig(
        head_dim=BENCH_CFG.head_dim,
        schedule=mixedkv.uniform(BENCH_CFG.num_layers),
        k_norm=rates.NORM_K8, v_norm=rates.NORM_V4_LOG, storage="bitpack"))
    backend = backends_lib.QuantPallasBackend(BENCH_CFG, qz, interpret=True)
    params, _ = transformer.init_params(jax.random.PRNGKey(0), BENCH_CFG)
    reqs = make_trace(p, args.seed)

    print("reference: mesh=None single-device engine ...", flush=True)
    ref = serve(params, backend, reqs, p, mesh=None)
    rows = {"single": ref}
    match = True
    for n in MESH_SIZES:
        print(f"mesh={n}: serving ...", flush=True)
        row = serve(params, backend, reqs, p, mesh_lib.make_sim_mesh(n))
        row["tokens_match"] = row["tokens"] == ref["tokens"]
        match = match and row["tokens_match"]
        rows[f"mesh{n}"] = row

    for r in rows.values():
        r.pop("tokens")  # parity is recorded; raw tokens would bloat the json

    base = rows["mesh1"]["per_device_pool_bytes"]
    report = {
        "meta": {
            "model": {k: getattr(BENCH_CFG, k) for k in
                      ("num_layers", "num_kv_heads", "head_dim", "d_model")},
            "trace": dict(p), "smoke": args.smoke,
            "backend": jax.default_backend(),
            "mesh_sizes": list(MESH_SIZES),
        },
        "tokens_match": match,
        "rows": rows,
        "summary": {
            "tokens_match": match,
            "ratios": {
                "per_device_bytes_n2":
                    rows["mesh2"]["per_device_pool_bytes"] / base,
                "per_device_bytes_n4":
                    rows["mesh4"]["per_device_pool_bytes"] / base,
            },
        },
    }
    args.out.write_text(json.dumps(report, indent=2) + "\n")
    print(f"wrote {args.out}")
    for name, r in rows.items():
        extra = ("" if name == "single"
                 else f"  tokens_match={r['tokens_match']}")
        print(f"  {name:>7}: per-device pool "
              f"{r['per_device_pool_bytes'] / 1024:8.1f} KiB  "
              f"wall {r['wall_s'] * 1e3:7.1f} ms{extra}")
    errs = []
    if not match:
        errs.append("sharded tokens diverged from the single-device engine")
    for n in (2, 4):
        ratio = report["summary"]["ratios"][f"per_device_bytes_n{n}"]
        if ratio > 1.02 / n:
            errs.append(f"{n}-way per-device pool bytes ratio {ratio:.3f} "
                        f"exceeds {1.02 / n:.3f} (want ~1/{n})")
    if any(r["post_warmup_variants"] != 0 for r in rows.values()):
        errs.append("post-warmup compilation detected")
    for e in errs:
        print(f"CHECK FAILED: {e}", file=sys.stderr)
    return 1 if errs else 0


if __name__ == "__main__":
    sys.exit(main())
