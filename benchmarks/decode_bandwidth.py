"""Decode-bandwidth benchmark: physical bytes-read-per-token and attend
throughput per backend x storage x context length.

Long-context decode is bound by reading the KV cache once per token, so the
number that decides tokens/sec at 16k+ context is *physical bytes streamed
per decoded token* — not the logical bit rate. This harness measures exactly
that for every serving backend:

    raw           bf16 cache (16 bits/elem reference)
    quant-xla     stored TurboAngle payload (capacity win; the path also
                  re-materializes dequantized y-domain K/V in HBM, reported
                  as `xla_dequant_bytes` — the traffic the kernel avoids)
    quant-pallas  the HBM stream the fused kernel actually reads: packed
                  uint32 words under storage="bitpack", or i32-widened
                  container codes under the legacy storage="uint8"

Emits BENCH_decode.json (the standing perf-regression baseline; CI runs
`--smoke` and validates it) and exits non-zero if the packed representation
fails to beat the container representation on bytes-read, or — at the
paper-scale context — if bitpack/uint8 on the Pallas path exceeds 0.55x
(i.e. the ~3.3-bit angle + packed-norm budget must be what physically moves
through the cache read path).

Usage:
    PYTHONPATH=src python benchmarks/decode_bandwidth.py [--smoke] \
        [--interpret] [--out BENCH_decode.json]

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

from repro.cache import kvcache
from repro.configs.base import ModelConfig
from repro.core import mixedkv, rates
from repro.core.quantizer import KVQuantizer, QuantizerConfig
from repro.serving import backends as backends_lib

# paper-scale head geometry (d=128 group), one layer: decode streams the
# cache per layer, so per-layer numbers are the unit that matters
BENCH_CFG = ModelConfig(
    name="bench-decode", family="decoder", num_layers=1, d_model=256,
    num_heads=2, num_kv_heads=1, d_ff=256, vocab_size=256, head_dim=128,
)
FULL_T = (1024, 4096, 16384)
SMOKE_T = (128, 256)
PALLAS_RATIO_BUDGET = 0.55  # bitpack/uint8 bytes-read on the kernel path


def _quantizer(storage: str) -> KVQuantizer:
    return KVQuantizer(QuantizerConfig(
        head_dim=BENCH_CFG.head_dim,
        schedule=mixedkv.uniform(BENCH_CFG.num_layers),  # K128V64
        k_norm=rates.NORM_K8, v_norm=rates.NORM_V4_LOG,
        storage=storage))


def _filled_quant_cache(qz: KVQuantizer, t: int, rng) -> kvcache.QuantKVCache:
    shape = (1, 1, t, BENCH_CFG.num_kv_heads, BENCH_CFG.head_dim)  # (L,B,...)
    k = jnp.asarray(rng.normal(size=shape), jnp.float32)
    v = jnp.asarray(rng.normal(size=shape), jnp.float32)
    nk, nv = qz.layer_bins()
    return kvcache.QuantKVCache(
        k=qz.encode(k, int(nk[0]), qz.config.k_norm),
        v=qz.encode(v, int(nv[0]), qz.config.v_norm),
        lengths=jnp.full((1,), t, jnp.int32),
    )


def _filled_raw_cache(t: int, rng) -> kvcache.RawKVCache:
    shape = (1, 1, t, BENCH_CFG.num_kv_heads, BENCH_CFG.head_dim)
    return kvcache.RawKVCache(
        k=jnp.asarray(rng.normal(size=shape), jnp.bfloat16),
        v=jnp.asarray(rng.normal(size=shape), jnp.bfloat16),
        lengths=jnp.full((1,), t, jnp.int32),
    )


def _time_attend(backend, cache, rng, reps: int) -> tuple[float, float]:
    """(compile seconds, steady-state seconds per call) for one layer's
    attend over the full cache.

    The first call pays trace + compile; lumping it into the timed reps
    (the pre-ISSUE-6 behavior) made every cell report mostly-compile at
    small T and hid steady-state regressions behind compile noise. Here
    the first call is timed separately and reported as `compile_s`; the
    median of the subsequent `reps` calls is the steady-state number every
    gate and ratio is computed from."""
    layer = (jax.tree.map(lambda a: a[0], cache.k),
             jax.tree.map(lambda a: a[0], cache.v))
    q = jnp.asarray(
        rng.normal(size=(1, 1, BENCH_CFG.num_heads, BENCH_CFG.head_dim)),
        jnp.float32)

    @jax.jit
    def fn(q, layer, lengths):
        return backend.attend(q, layer, 128, 64, lengths)

    t0 = time.perf_counter()
    fn(q, layer, cache.lengths).block_until_ready()  # trace + compile
    compile_s = time.perf_counter() - t0
    fn(q, layer, cache.lengths).block_until_ready()  # warm the caches
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(q, layer, cache.lengths).block_until_ready()
        times.append(time.perf_counter() - t0)
    return compile_s, float(np.median(times))


def _elements(t: int) -> int:
    """Stored elements per token-step read: K and V, padded head dim."""
    d_pad = 2 ** int(np.ceil(np.log2(BENCH_CFG.head_dim)))
    return 2 * t * BENCH_CFG.num_kv_heads * d_pad


def run(t_values, reps: int, interpret: bool) -> dict:
    rng = np.random.default_rng(0)
    rows = []
    for t in t_values:
        raw_be = backends_lib.RawBackend(BENCH_CFG)
        raw_cache = _filled_raw_cache(t, rng)
        raw_bytes = raw_be.attend_stream_bytes(raw_cache)
        csec, sec = _time_attend(raw_be, raw_cache, rng, reps)
        rows.append(dict(
            backend="raw", storage="bf16", T=t,
            bytes_read_per_token=raw_bytes,
            bits_per_element=raw_bytes * 8 / _elements(t),
            attend_ms=sec * 1e3, compile_s=csec, tokens_per_sec=1.0 / sec))
        for storage in ("uint8", "bitpack"):
            qz = _quantizer(storage)
            cache = _filled_quant_cache(qz, t, rng)
            for name in ("quant-xla", "quant-pallas"):
                be = backends_lib.get_backend(name, BENCH_CFG, qz,
                                              interpret=interpret)
                nbytes = be.attend_stream_bytes(cache)
                csec, sec = _time_attend(be, cache, rng, reps)
                row = dict(
                    backend=name, storage=storage, T=t,
                    bytes_read_per_token=nbytes,
                    bits_per_element=nbytes * 8 / _elements(t),
                    attend_ms=sec * 1e3, compile_s=csec,
                    tokens_per_sec=1.0 / sec)
                if name == "quant-xla":
                    # the fallback's extra HBM write+read: dequantized
                    # y-domain K/V at y_dtype (bf16)
                    row["xla_dequant_bytes"] = _elements(t) * 2
                rows.append(row)
    return rows


def summarize(rows) -> dict:
    by = {(r["backend"], r["storage"], r["T"]): r for r in rows}
    t_max = max(r["T"] for r in rows)
    summary = {"T_max": t_max, "ratios": {}, "attend_ratios": {}}
    for name in ("quant-xla", "quant-pallas"):
        for t in sorted({r["T"] for r in rows}):
            bp = by[(name, "bitpack", t)]["bytes_read_per_token"]
            u8 = by[(name, "uint8", t)]["bytes_read_per_token"]
            summary["ratios"][f"{name}@T={t}"] = bp / u8
            # steady-state wall ratio: the clock must follow the counters
            summary["attend_ratios"][f"{name}@T={t}"] = (
                by[(name, "bitpack", t)]["attend_ms"]
                / by[(name, "uint8", t)]["attend_ms"])
    summary["pallas_bitpack_over_uint8"] = summary["ratios"][
        f"quant-pallas@T={t_max}"]
    summary["pallas_bitpack_over_raw"] = (
        by[("quant-pallas", "bitpack", t_max)]["bytes_read_per_token"]
        / by[("raw", "bf16", t_max)]["bytes_read_per_token"])
    return summary


def check(report: dict) -> list[str]:
    """Regression invariants; returned list is empty on success."""
    errs = []
    rows = report.get("rows", [])
    keys = {"backend", "storage", "T", "bytes_read_per_token",
            "bits_per_element", "attend_ms", "compile_s", "tokens_per_sec"}
    for r in rows:
        if not keys <= set(r):
            errs.append(f"malformed row {r}")
    for key, ratio in report.get("summary", {}).get("ratios", {}).items():
        if ratio >= 1.0:
            errs.append(f"bitpack bytes-read >= uint8 bytes-read at {key}: "
                        f"{ratio:.3f}")
    if not report.get("meta", {}).get("smoke", True):
        # full mode only: steady-state wall must follow the byte counters
        # (smoke timings at tiny T are too noisy to gate in CI)
        for key, ratio in report.get("summary", {}).get(
                "attend_ratios", {}).items():
            if key.startswith("quant-pallas") and ratio > 1.0:
                errs.append(
                    f"pallas bitpack attend slower than uint8 at {key}: "
                    f"{ratio:.3f}x — the packed stream's byte win is not "
                    "reaching the clock")
    ratio = report.get("summary", {}).get("pallas_bitpack_over_uint8")
    if ratio is None:
        errs.append("missing summary.pallas_bitpack_over_uint8")
    elif ratio > PALLAS_RATIO_BUDGET:
        errs.append(
            f"pallas bitpack/uint8 bytes-read {ratio:.3f} exceeds the "
            f"{PALLAS_RATIO_BUDGET} budget — the packed stream is not what "
            "the kernel reads")
    return errs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI (interpret-mode friendly)")
    ap.add_argument("--out", type=Path, default=Path("BENCH_decode.json"))
    ap.add_argument("--reps", type=int, default=0,
                    help="timing reps per cell (0 -> 1 smoke / 3 full)")
    ap.add_argument("--interpret", action="store_true",
                    help="run the Pallas kernels in the interpreter (CPU); "
                         "without it they are compiled, which needs a TPU")
    args = ap.parse_args(argv)
    if args.interpret and jax.default_backend() == "tpu":
        ap.error("--interpret on a TPU would time the Pallas interpreter "
                 "and label it tpu; drop --interpret")
    t_values = SMOKE_T if args.smoke else FULL_T
    reps = args.reps or (1 if args.smoke else 3)
    rows = run(t_values, reps, args.interpret)
    report = {
        "meta": {
            "model": {k: getattr(BENCH_CFG, k) for k in
                      ("num_layers", "num_kv_heads", "head_dim")},
            "schedule": "K128V64",
            "k_norm": rates.NORM_K8.describe(),
            "v_norm": rates.NORM_V4_LOG.describe(),
            "smoke": args.smoke,
            "backend": jax.default_backend(),
            "interpret": args.interpret,
        },
        "rows": rows,
        "summary": summarize(rows),
    }
    args.out.write_text(json.dumps(report, indent=2) + "\n")
    print(f"wrote {args.out}")
    for r in rows:
        print(f"  {r['backend']:>12} {r['storage']:>7} T={r['T']:>6} "
              f"{r['bytes_read_per_token']:>10} B/token "
              f"({r['bits_per_element']:.2f} bits/elem) "
              f"attend {r['attend_ms']:.2f} ms "
              f"(compile {r['compile_s']:.2f} s)")
    for k, v in report["summary"]["ratios"].items():
        print(f"  bytes ratio {k}: {v:.3f}")
    for k, v in report["summary"]["attend_ratios"].items():
        print(f"  attend ratio {k}: {v:.3f}")
    errs = check(report)
    for e in errs:
        print(f"CHECK FAILED: {e}", file=sys.stderr)
    return 1 if errs else 0


if __name__ == "__main__":
    raise SystemExit(main())
