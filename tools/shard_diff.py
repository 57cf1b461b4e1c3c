#!/usr/bin/env python
"""Run one serving trace at mesh sizes {1, 2, 4} and diff EVERYTHING.

The sharded paged engine's contract is bitwise equivalence with the
single-device engine (docs/sharding.md). tests/test_sharded.py asserts
token parity inside pytest; this tool is the standalone CI gate
(`shard-smoke` job) and the first debugging stop when parity breaks —
it reports WHICH surface diverged, field by field:

  * per-request greedy tokens (the headline contract),
  * final page-table rows + allocator occupancy (replicated scheduler
    state must march in lockstep across mesh sizes),
  * every deterministic `stats[...]` field — dispatch counts, token
    counters, SLO ladder actions, speculation accounting — wall-clock
    and latency fields excluded by name.

Exit status: 0 when every mesh size matches the mesh=None reference,
1 on any divergence.

A CPU rehearsal tool: it forces the CPU platform and simulated host
devices, and runs the Pallas kernels in the interpreter. The mesh path on
real chips is run by `python chip_smoke.py --chips 4`.

Needs >= 4 simulated devices; run as

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python tools/shard_diff.py [--backend quant-pallas]
"""
from __future__ import annotations

import argparse
import os
import sys

if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

# stats fields that legitimately vary run-to-run (timing) — everything
# else in the stats dict must be identical across mesh sizes
NONDET = ("wall", "latency", "ttft", "tpot", "tokens_per_sec", "_s")


def _deterministic(d, prefix=""):
    """Flatten a stats dict to {dotted.key: value}, dropping timing."""
    out = {}
    for k, v in sorted(d.items()):
        key = f"{prefix}{k}"
        if any(p in k for p in NONDET):
            continue
        if isinstance(v, dict):
            out.update(_deterministic(v, key + "."))
        elif isinstance(v, (int, bool, str)):
            out[key] = v
        elif isinstance(v, float):
            out[key] = round(v, 12)
        elif isinstance(v, (list, tuple)):
            out[key] = str(v)
    return out


def run_trace(mesh_size, backend_name, seed=0):
    """Serve the canonical trace; returns (tokens, tables, alloc, stats)."""
    import jax

    from repro.configs.base import ModelConfig
    from repro.core import mixedkv, rates
    from repro.core.quantizer import KVQuantizer, QuantizerConfig
    from repro.launch import mesh as mesh_lib
    from repro.models import transformer
    from repro.serving import backends as backends_lib
    from repro.serving import scheduler as sched_lib

    cfg = ModelConfig(name="shard-diff", family="decoder", num_layers=2,
                      d_model=64, num_heads=8, num_kv_heads=8, d_ff=64,
                      vocab_size=128, head_dim=8)
    qz = KVQuantizer(QuantizerConfig(
        head_dim=cfg.head_dim, schedule=mixedkv.uniform(cfg.num_layers),
        k_norm=rates.NORM_K8, v_norm=rates.NORM_V4_LOG, storage="bitpack"))
    if backend_name == "quant-pallas":
        backend = backends_lib.QuantPallasBackend(cfg, qz, interpret=True)
    else:
        backend = backends_lib.QuantXLABackend(cfg, qz)
    params, _ = transformer.init_params(jax.random.PRNGKey(seed), cfg)
    mesh = (None if mesh_size is None
            else mesh_lib.make_sim_mesh(mesh_size))
    sc = sched_lib.SchedulerConfig(
        num_slots=2, page_size=8, num_pages=64, max_context=64,
        prefill_chunk=8, max_burst=4, debug_conservation=True, mesh=mesh)
    eng = sched_lib.PagedServingEngine(params, cfg, backend, sc)
    eng.warmup()
    rng = np.random.default_rng(seed + 1)
    reqs = [sched_lib.Request(
        rid=i, tokens=rng.integers(1, 127, size=int(n)).astype(np.int32),
        max_new_tokens=6, arrival=0.0)
        for i, n in enumerate([5, 19, 11, 30])]
    results, stats = eng.run(reqs)
    eng.allocator.check_conservation()
    tokens = {r.rid: [int(t) for t in r.tokens] for r in results}
    tables = np.asarray(eng.page_table).tolist()
    alloc = dict(num_free=eng.allocator.num_free,
                 num_live=eng.allocator.num_live,
                 total_refs=eng.allocator.total_refs,
                 live_pages=sorted(eng.allocator.live_pages()))
    return tokens, tables, alloc, _deterministic(stats)


def diff_surface(name, ref, got, failures):
    if ref == got:
        return
    if isinstance(ref, dict) and isinstance(got, dict):
        for k in sorted(set(ref) | set(got)):
            a, b = ref.get(k, "<missing>"), got.get(k, "<missing>")
            if a != b:
                failures.append(f"  {name}[{k}]: ref={a!r}  got={b!r}")
    else:
        failures.append(f"  {name}: ref={ref!r}  got={got!r}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--backend", default="quant-pallas",
                    choices=["quant-pallas", "quant-xla"])
    ap.add_argument("--mesh-sizes", type=int, nargs="+", default=[1, 2, 4])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    have = len(jax.devices())
    need = max(args.mesh_sizes)
    if have < need:
        print(f"FATAL: need {need} simulated devices, have {have} — set "
              f"XLA_FLAGS=--xla_force_host_platform_device_count=8 before "
              f"any jax import", file=sys.stderr)
        return 2

    print(f"reference: mesh=None single-device engine "
          f"[{args.backend}] ...", flush=True)
    ref = run_trace(None, args.backend, args.seed)
    print(f"  {sum(len(t) for t in ref[0].values())} tokens over "
          f"{len(ref[0])} requests")

    ok = True
    for n in args.mesh_sizes:
        print(f"mesh={n}: serving the same trace ...", flush=True)
        got = run_trace(n, args.backend, args.seed)
        failures: list[str] = []
        diff_surface("tokens", ref[0], got[0], failures)
        if ref[1] != got[1]:
            failures.append(f"  page_table: ref={ref[1]!r}  got={got[1]!r}")
        diff_surface("allocator", ref[2], got[2], failures)
        diff_surface("stats", ref[3], got[3], failures)
        if failures:
            ok = False
            print(f"mesh={n}: DIVERGED on {len(failures)} field(s):")
            for line in failures:
                print(line)
        else:
            print(f"mesh={n}: identical tokens, page tables, allocator "
                  f"state, {len(ref[3])} deterministic stats fields")
    print("PASS: every mesh size matches the single-device reference"
          if ok else "FAIL: sharded serving diverged from single-device")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
