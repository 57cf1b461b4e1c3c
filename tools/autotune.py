"""CLI for the quantized-attention kernel autotuner.

Measures the block_t candidates on the benchmark geometry
(the paper-scale head group `benchmarks/decode_bandwidth.py` times) and
caches the winners per (geometry, backend, platform) — see
`repro.kernels.qattn.autotune` for what is tuned and why. The cache is a
JSON file ($REPRO_AUTOTUNE_CACHE or ~/.cache/repro/qattn_autotune.json);
`autotune.tuned_backend` applies it without re-measuring. The serving
path never reads it.

Usage:
    PYTHONPATH=src python tools/autotune.py --print     # show the cache
    PYTHONPATH=src python tools/autotune.py --refresh   # (re-)measure, TPU
    PYTHONPATH=src python tools/autotune.py --smoke --interpret  # CPU, CI
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.configs.base import ModelConfig  # noqa: E402
from repro.core import mixedkv, rates  # noqa: E402
from repro.core.quantizer import KVQuantizer, QuantizerConfig  # noqa: E402
from repro.kernels.qattn import autotune as at  # noqa: E402

# the decode-bandwidth benchmark geometry: one paper-scale head group
TUNE_CFG = ModelConfig(
    name="autotune", family="decoder", num_layers=1, d_model=256,
    num_heads=2, num_kv_heads=1, d_ff=256, vocab_size=256, head_dim=128,
)


def _qz(storage: str) -> KVQuantizer:
    return KVQuantizer(QuantizerConfig(
        head_dim=TUNE_CFG.head_dim,
        schedule=mixedkv.uniform(TUNE_CFG.num_layers),
        k_norm=rates.NORM_K8, v_norm=rates.NORM_V4_LOG, storage=storage))


def show(cache_path: Path | None) -> None:
    entries = at.load_cache(cache_path)
    path = cache_path or at.default_cache_path()
    if not entries:
        print(f"autotune cache {path}: empty (run with --refresh)")
        return
    print(f"autotune cache {path}: {len(entries)} entries")
    for key, e in sorted(entries.items()):
        print(f"  {key}")
        print(f"    best: block_t={e['block_t']} "
              f"page_size={e['page_size']} ({e['attend_ms']:.2f} ms @ "
              f"T={e['t']})")
        for cand, ms in sorted(e.get("measured", {}).items(),
                               key=lambda kv: kv[1]):
            print(f"    {cand:<28} {ms:8.2f} ms")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--print", action="store_true", dest="show",
                    help="print the cache and exit (never measures)")
    ap.add_argument("--refresh", action="store_true",
                    help="re-measure even if an entry is cached")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny context + candidate set (CI-sized)")
    ap.add_argument("--t", type=int, default=0,
                    help="context length to measure at (0 -> auto)")
    ap.add_argument("--reps", type=int, default=0,
                    help="timing reps per candidate (0 -> auto)")
    ap.add_argument("--interpret", action="store_true",
                    help="time the kernel in the Pallas interpreter (on "
                         "CPU); without it the kernel is compiled, which "
                         "needs a TPU")
    ap.add_argument("--cache", type=Path, default=None,
                    help="cache file (default: $REPRO_AUTOTUNE_CACHE)")
    args = ap.parse_args(argv)
    if args.show:
        show(args.cache)
        return 0
    t = args.t or (256 if args.smoke else 1024)
    reps = args.reps or (1 if args.smoke else 3)
    block_ts = (64, 128, 256) if args.smoke else None
    for storage in ("bitpack", "uint8"):
        qz = _qz(storage)
        entry = at.autotune(TUNE_CFG, qz, t=t, reps=reps,
                            block_ts=block_ts, cache_path=args.cache,
                            refresh=args.refresh, interpret=args.interpret)
        print(f"storage={storage}: block_t={entry['block_t']} "
              f"page_size={entry['page_size']} "
              f"({entry['attend_ms']:.2f} ms @ T={entry['t']})")
    show(args.cache)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
