#!/usr/bin/env python3
"""Smoke test on the chip: serve full-width qwen3-0.6b through the paged
engine with compiled Pallas attention, and check what comes out.

    python chip_smoke.py             # one TPU chip
    python chip_smoke.py --chips 4   # the kv-head-sharded mesh path, 4 chips

One process does everything and starts no other process. It needs a TPU:
when JAX finds none, or when this file is not in a checkout of the
repository (no src/repro beside it), it exits non-zero and prints no
result.

One chip. `qwen3-0.6b` at its published widths (28 layers, d_model 1024,
16 heads over 8 kv heads, head_dim 64, vocab 151,936) with seeded random
f32 weights, and its early-boost KV quantization stored bit-packed. Four
requests with prompts of 128-480 tokens (from --seed) decode 32 tokens
each through `PagedServingEngine` with a `QuantPallasBackend` compiled for
the chip (interpret=False): `engine.warmup()`, then `engine.run()`. The
phase fails when
  * a request returns other than 32 tokens, or not "completed";
  * `post_warmup_variants` > 0 (a shape was compiled inside the run);
  * the compiled decode step holds no `tpu_custom_call`, i.e. the Pallas
    kernel did not run compiled;
  * layer 0's paged Pallas attention over the engine's pool disagrees
    with the quant-xla path (y_dtype float32) beyond ATTN_TOL;
  * one public paged decode step over that pool gives non-finite logits.

Four chips (--chips 4). The same requests through the same engine, once on
one chip and once with `SchedulerConfig.mesh` a ("data", "model") = (1, 4)
mesh that splits the pool's kv heads four ways. It fails when greedy tokens
differ for any request, when a device holds other than a quarter of the
pool, or when the params or the pool are not committed to the mesh.

The last line of standard output is
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
ARCH = "qwen3-0.6b"
SLOTS = 4
PAGE_SIZE = 16
PREFILL_CHUNK = 32
GEN = 32
PROMPT_LENS = (128, 480)  # inclusive range the prompt lengths are drawn from

# Paged Pallas attention vs the quant-xla path on the same pool. Both
# dequantize the same codes in f32 and take their dots at full f32
# precision (the kernel's dots are HIGHEST; the reference runs under
# default_matmul_precision("highest")), so what separates them is the
# chip's cos/sin/exp against XLA's and the summation order: a max |diff|
# of 1.9e-6 at |ref| up to 1.25 on a TPU v5e. 1e-5, absolute and
# relative, leaves 5x room over that, and is tight enough to fail a
# kernel whose dots fell back to one bf16 pass (a lost Precision.HIGHEST),
# which errs by far more; PERF.md records both readings.
ATTN_TOL = 1e-5


class SmokeFailure(Exception):
    """A check of the smoke test failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def make_requests(vocab: int, seed: int):
    from repro.serving import scheduler

    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, SLOTS)
    return [scheduler.Request(
        rid=i, tokens=rng.integers(0, vocab, n).astype(np.int32),
        max_new_tokens=GEN) for i, n in enumerate(lens)]


def sched_config(requests, mesh=None):
    """Slots, pages and context sized to the requests the way
    `repro.launch.serve --paged` sizes them."""
    from repro.serving import pages, scheduler

    longest = max(len(r.tokens) for r in requests)
    max_context = -(-longest // PREFILL_CHUNK) * PREFILL_CHUNK + GEN
    return scheduler.SchedulerConfig(
        num_slots=SLOTS, page_size=PAGE_SIZE,
        num_pages=1 + SLOTS * pages.pages_for_tokens(max_context, PAGE_SIZE),
        max_context=max_context, prefill_chunk=PREFILL_CHUNK, mesh=mesh)


def build(cfg_id: str, interpret: bool, seed: int, reduced: bool = False):
    """(cfg, quantizer, quant-pallas backend, params) for a registry id."""
    import jax

    from repro.configs import registry
    from repro.launch import steps
    from repro.models import transformer
    from repro.serving import backends

    run = registry.get_run_config(cfg_id)
    if reduced:  # CPU rehearsal only; the chip always runs full width
        import dataclasses
        run = dataclasses.replace(run,
                                  model=registry.get_reduced_config(cfg_id))
    cfg = run.model
    qz = steps.make_quantizer(run)
    backend = backends.QuantPallasBackend(cfg, qz, interpret=interpret)
    params, _ = transformer.init_params(jax.random.PRNGKey(seed), cfg)
    return cfg, qz, backend, params


def serve(params, cfg, backend, requests, seed: int, mesh=None):
    """Warm up a paged engine, serve the requests, check the results."""
    import jax

    from repro.serving import scheduler

    eng = scheduler.PagedServingEngine(params, cfg, backend,
                                       sched_config(requests, mesh))
    warm = eng.warmup()
    results, stats = eng.run(requests, rng=jax.random.PRNGKey(seed))
    got = [len(r.tokens) for r in results]
    print(f"  warm-up: {warm['variants']} variants, compile wall "
          f"{warm['compile_wall_s']:.1f} s, warm-up wall "
          f"{warm['warmup_wall_s']:.1f} s")
    print(f"  post_warmup_variants: {stats['perf']['post_warmup_variants']}")
    print(f"  tokens per request: {got} (prompts "
          f"{[r.prompt_len for r in results]})")
    print(f"  pool bytes: {stats['pool_bytes']}")
    check(all(r.status == "completed" for r in results),
          f"request statuses {[r.status for r in results]}")
    check(got == [GEN] * len(requests), f"tokens per request {got}")
    check(stats["perf"]["post_warmup_variants"] == 0,
          f"{stats['perf']['post_warmup_variants']} variants compiled "
          f"after warm-up")
    return eng, results, stats


def check_table(requests, num_pages: int, max_pages: int, seed: int):
    """A seeded page table over the pool's data pages and each slot's
    prompt length: a valid attention problem over real served codes."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    ids = rng.permutation(np.arange(1, num_pages))[:SLOTS * max_pages]
    lengths = [len(r.tokens) for r in requests]
    return (jnp.asarray(ids.reshape(SLOTS, max_pages), jnp.int32),
            jnp.asarray(lengths, jnp.int32))


def attention_check(eng, cfg, qz, backend, requests, seed: int) -> None:
    """Layer 0 of the engine's pool through paged Pallas attention and
    through the quant-xla path, same pages, same queries."""
    import jax
    import jax.numpy as jnp

    from repro.serving import backends

    table, lengths = check_table(requests, eng.sched.num_pages,
                                 eng.sched.max_pages, seed)
    layer = tuple(jax.tree.map(lambda a: a[0], p)
                  for p in (eng.pool.k, eng.pool.v))
    nk, nv = qz.layer_bins()
    q = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (SLOTS, 1, cfg.num_heads, cfg.head_dim),
                          jnp.float32)
    xla = backends.QuantXLABackend(cfg, qz, y_dtype=jnp.float32)

    def attend(be):
        return jax.jit(lambda q, c, t, n: be.paged_attend(
            q, c, nk[0], nv[0], t, n))(q, layer, table, lengths)

    got = np.asarray(attend(backend))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(attend(xla))
    err = float(np.max(np.abs(got - want)))
    print(f"  layer-0 paged attention, Pallas vs quant-xla: max |diff| "
          f"{err:.3e} over {got.size} values (max |ref| "
          f"{float(np.max(np.abs(want))):.3e}; tolerance {ATTN_TOL})")
    check(bool(np.isfinite(got).all()), "non-finite Pallas attention")
    check(np.allclose(got, want, rtol=ATTN_TOL, atol=ATTN_TOL),
          f"paged Pallas attention off the quant-xla path by {err:.3e}")


def logits_check(eng, cfg, backend, requests, params, seed: int) -> None:
    """One public paged decode step over the served pool: finite logits
    of shape (slots, vocab)."""
    import jax
    import jax.numpy as jnp

    from repro.serving import decode, pages

    table, lengths = check_table(requests, eng.sched.num_pages,
                                 eng.sched.max_pages, seed)
    cache = pages.PagedKVCache(eng.pool.k, eng.pool.v, table, lengths)
    tokens = jnp.asarray([[int(r.tokens[-1])] for r in requests], jnp.int32)
    active = jnp.ones((SLOTS,), bool)
    logits, _ = jax.jit(lambda p, c, t, a: decode.decode_step_paged(
        p, cfg, c, t, a, backend=backend))(params, cache, tokens, active)
    logits = np.asarray(logits)
    finite = bool(np.isfinite(logits).all())
    print(f"  decode-step logits: shape {logits.shape}, finite {finite}")
    check(logits.shape == (SLOTS, cfg.vocab_size),
          f"logits shape {logits.shape}")
    check(finite, "non-finite logits")


def one_chip(seed: int, interpret: bool = False, reduced: bool = False):
    cfg, qz, backend, params = build(ARCH, interpret, seed, reduced)
    print(f"{cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.num_heads} heads / {cfg.num_kv_heads} kv heads, head_dim "
          f"{cfg.head_dim}, vocab {cfg.vocab_size}; KV "
          f"{qz.config.schedule.describe()} "
          f"stored {qz.config.resolved_storage}")
    requests = make_requests(cfg.vocab_size, seed)
    eng, _, _ = serve(params, cfg, backend, requests, seed)
    if not interpret:
        text = eng.executable(("decode", eng.sched.max_pages)).as_text()
        has_kernel = "tpu_custom_call" in text
        print(f"  compiled decode step holds tpu_custom_call: {has_kernel}")
        check(has_kernel, "no tpu_custom_call in the compiled decode step")
    attention_check(eng, cfg, qz, backend, requests, seed)
    logits_check(eng, cfg, backend, requests, params, seed)


def four_chips(seed: int, devices, interpret: bool = False,
               reduced: bool = False):
    import jax

    from repro.distributed import sharding
    from repro.launch import mesh as mesh_lib

    cfg, qz, backend, params = build(ARCH, interpret, seed, reduced)
    requests = make_requests(cfg.vocab_size, seed)
    print("one chip:")
    _, single, stats1 = serve(params, cfg, backend, requests, seed)
    mesh = mesh_lib.make_sim_mesh(4, devices)
    print(f"mesh {dict(mesh.shape)}:")
    eng, sharded, _ = serve(params, cfg, backend, requests, seed, mesh=mesh)
    same = [bool(np.array_equal(a.tokens, b.tokens))
            for a, b in zip(single, sharded)]
    print(f"  greedy tokens equal to one chip, per request: {same}")
    check(all(same), f"mesh tokens differ from one chip: {same}")
    pool_leaves = jax.tree.leaves((eng.pool.k, eng.pool.v))
    want_spec = sharding.paged_pool_pspec()
    pool_sharding = jax.sharding.NamedSharding(mesh, want_spec)
    check(all(a.sharding.is_equivalent_to(pool_sharding, a.ndim)
              for a in pool_leaves), "pool not committed to the mesh")
    check(all(a.sharding.mesh == mesh and a.sharding.is_fully_replicated
              for a in jax.tree.leaves(eng.params)),
          "params not replicated over the mesh")
    per_dev = {d: 0 for d in mesh.devices.flat}
    for a in pool_leaves:
        for s in a.addressable_shards:
            per_dev[s.device] += s.data.nbytes
    ratios = [per_dev[d] / stats1["pool_bytes"] for d in mesh.devices.flat]
    print(f"  per-device pool bytes: {list(per_dev.values())} = "
          f"{[round(r, 4) for r in ratios]} of one chip's "
          f"{stats1['pool_bytes']}")
    check(all(abs(r - 0.25) < 0.01 for r in ratios),
          f"per-device pool share {ratios}, not a quarter")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the kv-head-sharded mesh path and "
                         "its one-chip comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke.py: no src/repro beside this script; run it from "
              "a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke.py: JAX found no TPU (platform {platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke.py: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    from repro.launch import jax_cache

    print(f"device: {devices[0].device_kind} x {len(devices)}; compile "
          f"cache {jax_cache.use_persistent_cache()}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips(args.seed, devices)
        else:
            one_chip(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke.py: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"wall {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
