import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    # stop XLA storing bf16 remat checkpoints upcast to f32 (doubles
    # the per-layer residual stack at 405B)
    "--xla_allow_excess_precision=false "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell on the production meshes, capture memory/cost analyses and the
collective schedule for the roofline report.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch mistral-7b \
        --shape train_4k [--multi-pod] [--out artifacts/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all

The XLA_FLAGS line above MUST run before any other jax-touching import:
jax locks the device count at first backend init. Only the dry-run uses 512
placeholder host devices — tests and benches see the real single device.
"""
import argparse
import dataclasses
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax
import numpy as np

from repro.configs import registry
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_production_mesh
from repro.models.common import SHAPES
from repro.training.optimizer import AdamWConfig

# v5e-ish hardware constants (per chip) for the roofline terms
PEAK_FLOPS = 197e12  # bf16
HBM_BW = 819e9  # bytes/s
ICI_BW = 50e9  # bytes/s/link

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def _dtype_bytes(name: str) -> float:
    sizes = {
        "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
        "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
        "s8": 1, "u8": 1, "pred": 1, "s4": 0.5, "u4": 0.5,
    }
    return sizes.get(name, 4)


def collective_bytes(hlo_text: str) -> dict:
    """Sum output-shape bytes of every collective op in optimized HLO.

    Output size is the right proxy: all-gather output = gathered bytes,
    all-reduce output = reduced tensor, reduce-scatter output = shard.
    """
    out: dict = {c: 0.0 for c in _COLLECTIVES}
    # e.g.: %ag = bf16[16,4096,16384]{...} all-gather(...)
    pat = re.compile(
        r"=\s+(?:\(([^)]*)\)|(\w+)\[([\d,]*)\][^ ]*)\s+"
        r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    )
    for m in pat.finditer(hlo_text):
        op = m.group(4)
        nbytes = 0.0
        if m.group(1) is not None:  # tuple shape
            for part in m.group(1).split(","):
                part = part.strip()
                tm = re.match(r"(\w+)\[([\d,]*)\]", part)
                if tm:
                    dims = [int(x) for x in tm.group(2).split(",") if x]
                    nbytes += float(np.prod(dims)) * _dtype_bytes(tm.group(1))
        else:
            dims = [int(x) for x in m.group(3).split(",") if x]
            nbytes += float(np.prod(dims)) * _dtype_bytes(m.group(2))
        out[op] += nbytes
    out["total"] = sum(out[c] for c in _COLLECTIVES)
    return out


def analyze_compiled(compiled, n_chips: int) -> dict:
    """Roofline terms from one compiled executable."""
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))
    # cost_analysis reports per-device numbers for SPMD modules
    t_compute = flops / PEAK_FLOPS
    t_memory = bytes_accessed / HBM_BW
    t_collective = coll["total"] / n_chips / ICI_BW
    terms = {
        "flops_per_device": flops,
        "bytes_per_device": bytes_accessed,
        "collective_bytes_total": coll["total"],
        "collective_breakdown": {k: v for k, v in coll.items()
                                 if k != "total"},
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_collective,
        "bottleneck": max(
            [("compute", t_compute), ("memory", t_memory),
             ("collective", t_collective)],
            key=lambda kv: kv[1])[0],
        "memory_analysis": {
            "argument_size_bytes": mem.argument_size_in_bytes,
            "output_size_bytes": mem.output_size_in_bytes,
            "temp_size_bytes": mem.temp_size_in_bytes,
            "generated_code_size_bytes": mem.generated_code_size_in_bytes,
        },
    }
    return terms


def _abstract_inputs(art, kind: str):
    if kind == "train":
        return (art.param_shapes, art.opt_shapes, art.batch_shapes)
    if kind == "prefill":
        return (art.param_shapes, art.input_shapes["batch"])
    return (art.param_shapes, art.input_shapes["state"],
            art.input_shapes["tokens"])


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             seq_parallel: bool = True, quant_enabled: bool | None = None,
             microbatch: int | None = None) -> dict:
    """Lower + compile one cell; returns the analysis record."""
    shape = SHAPES[shape_name]
    run = registry.get_run_config(arch)
    skip = registry.shape_skip_reason(run.model, shape)
    if skip:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped", "reason": skip}

    # big-model numerics: bf16 params for the dry-run (fp32 never fits 405B
    # on one pod); int8 Adam moments for the giants
    big = run.model.param_count() > 20e9
    batch_shards = 32 if multi_pod else 16
    n_devices = batch_shards * 16
    m = run.model
    expert_bytes = m.moe_experts * 3 * m.d_model * m.d_ff * 2
    # small-expert MoE (granite): dispatch over every device and replicate
    # expert weights; big experts (mixtral): groups = batch shards, expert
    # weights stay tensor-parallel over "model"
    moe_groups = n_devices if expert_bytes < 512e6 else batch_shards
    model = dataclasses.replace(
        run.model,
        param_dtype="bfloat16" if big else "float32",
        compute_dtype="bfloat16",
        moe_dispatch_groups=moe_groups,
    )
    if quant_enabled is not None:
        run = dataclasses.replace(
            run, quant=dataclasses.replace(run.quant, enabled=quant_enabled))
    if microbatch is not None:
        run = dataclasses.replace(
            run, parallel=dataclasses.replace(run.parallel,
                                              microbatch=microbatch))
    if big:
        run = dataclasses.replace(
            run, parallel=dataclasses.replace(run.parallel,
                                              accum_dtype="bfloat16"))
    run = dataclasses.replace(run, model=model)

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    with mesh:
        if shape.kind == "train":
            opt_cfg = AdamWConfig(
                state_dtype="int8" if big else "float32")
            art = steps_lib.make_train_step(
                run, mesh, opt_cfg, shape, seq_parallel=seq_parallel)
            lowered = art.step_fn.lower(*_abstract_inputs(art, "train"))
        elif shape.kind == "prefill":
            art = steps_lib.make_prefill_step(
                run, mesh, shape, seq_parallel=seq_parallel)
            lowered = art.step_fn.lower(*_abstract_inputs(art, "prefill"))
        else:
            art = steps_lib.make_decode_step(run, mesh, shape)
            lowered = art.step_fn.lower(*_abstract_inputs(art, "decode"))
        compiled = lowered.compile()
    elapsed = time.time() - t0

    rec = {
        "arch": arch,
        "shape": shape_name,
        "multi_pod": multi_pod,
        "status": "ok",
        "n_chips": n_chips,
        "kind": shape.kind,
        "compile_seconds": round(elapsed, 1),
        "quant_enabled": bool(steps_lib.make_quantizer(run) is not None),
    }
    rec.update(analyze_compiled(compiled, n_chips))
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every (arch x shape) on the single-pod mesh plus "
                         "the multi-pod pass")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-quant", action="store_true",
                    help="disable TurboAngle (fp16-cache baseline)")
    ap.add_argument("--no-seq-parallel", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args(argv)

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    cells = []
    archs = registry.ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if (args.all or args.both_meshes) \
        else [args.multi_pod]

    failures = 0
    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape_name}__{'2pod' if mp else '1pod'}"
                try:
                    rec = run_cell(
                        arch, shape_name, multi_pod=mp,
                        seq_parallel=not args.no_seq_parallel,
                        quant_enabled=False if args.no_quant else None)
                except Exception as e:  # a failed cell is a bug — surface it
                    rec = {"arch": arch, "shape": shape_name,
                           "multi_pod": mp, "status": "FAILED",
                           "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-2000:]}
                    failures += 1
                cells.append(rec)
                (out_dir / f"{tag}.json").write_text(json.dumps(rec, indent=2))
                status = rec["status"]
                extra = ""
                if status == "ok":
                    extra = (f" bottleneck={rec['bottleneck']}"
                             f" t_comp={rec['t_compute_s']:.4f}s"
                             f" t_mem={rec['t_memory_s']:.4f}s"
                             f" t_coll={rec['t_collective_s']:.4f}s"
                             f" compile={rec['compile_seconds']}s")
                elif status == "skipped":
                    extra = f" ({rec['reason'][:60]})"
                else:
                    extra = f" {rec['error'][:200]}"
                print(f"[{status:>7}] {tag}{extra}", flush=True)

    (out_dir / "summary.json").write_text(json.dumps(cells, indent=2))
    print(f"\n{len(cells)} cells, {failures} failures -> {out_dir}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
