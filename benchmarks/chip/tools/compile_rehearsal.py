#!/usr/bin/env python3
"""Compile a cell's largest step programs for a described TPU v5e, with
no chip attached, and print each program's memory analysis.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/tools/compile_rehearsal.py \
        --workload deepseek-7b-l8.reason-decode [--pages 100] \
        [--set '{"slots": 8}']

Nothing runs and no weight or pool is allocated: the engine is built over
abstract shapes. For each program it prints the argument, output, alias
and temporary bytes, or the compiler's refusal. The chip's own compile
refuses the same programs at the same bytes, so a cell's pool is sized
here first; the allocator's peak on the chip then reads far below the
planned bytes (PERF.md).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--kinds", default="decode,prefill,prefix_load")
    ap.add_argument("--pages", type=int, default=0,
                    help="pool pages instead of the cell's own")
    ap.add_argument("--set", default="{}",
                    help="JSON object of traffic-mix keys to override")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    import jax
    from jax.experimental import topologies

    import harness
    import traffic as traffic_lib
    from repro.models import transformer
    from repro.serving import backends, compile_cache, pages, scheduler

    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.load_cell(args.workload)
    cell.mix.update(json.loads(args.set))
    jax.config.update("jax_default_matmul_precision",
                      cell.config["serving"]["matmul_precision"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = jax.sharding.SingleDeviceSharding(topo.devices[0])
    cfg = cell.arch.model_config(cell.config)
    qz = harness.quantizer(cell.config["serving"]["kv_quant"],
                           cell.arch.attention_layers(cell.config))
    tr = traffic_lib.generate(cell.mix, cfg.vocab_size, 0, args.seconds)
    sz = harness.sizes(cell.mix, tr.window, tr.documents)
    if args.pages:
        import dataclasses
        sz = dataclasses.replace(sz, num_pages=args.pages,
                                 prefix_pages=min(sz.prefix_pages,
                                                  args.pages - 2))
    sched = harness.scheduler_config(cell.mix, sz)
    backend = backends.QuantPallasBackend(cfg, qz, interpret=True)
    object.__setattr__(backend, "interpret", False)  # compile, never run
    object.__setattr__(
        backend, "init_paged_cache",
        lambda *a: jax.eval_shape(lambda: pages.init_paged_cache(
            cfg, qz, *a)))
    params, _ = transformer.abstract_params(cfg)
    eng = scheduler.PagedServingEngine(params, cfg, backend, sched)

    def nbytes(tree):
        return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))

    resident = nbytes(params) + nbytes((eng.pool.k, eng.pool.v))
    print(f"{args.workload}: max_context {sz.max_context}, pages "
          f"{sz.num_pages}, weights {nbytes(params)} B, pool "
          f"{nbytes((eng.pool.k, eng.pool.v))} B, resident {resident} B")
    need_pf, need_w = harness.reachable(cell.mix, tr.window, eng, cell.arch)
    want_pf = set(need_pf) | {cell.arch.prefill_key(eng, len(d), 0)
                              for d in tr.documents}
    skips = sorted({k[1] for k in want_pf})
    kinds = args.kinds.split(",")
    for key, fn, sds in compile_cache.enumerate_variants(eng, skips=skips):
        if key[0] not in kinds:
            continue
        if key[0] == "decode" and key[1] != max(need_w):
            continue
        if key[0] == "prefill" and key[1:] not in want_pf:
            continue
        if key[0] == "prefix_load" and key[1] != max(skips) // sched.page_size:
            continue
        placed = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            sds)
        try:
            ma = fn.lower(*placed).compile().memory_analysis()
        except jax.errors.JaxRuntimeError as e:
            msg = str(e)
            print(f"  {key}: refused: {msg[:msg.find(chr(10))]}", flush=True)
            continue
        peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
        print(f"  {key}: args {ma.argument_size_in_bytes} out "
              f"{ma.output_size_in_bytes} alias {ma.alias_size_in_bytes} "
              f"temp {ma.temp_size_in_bytes} -> program peak {peak} B",
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
