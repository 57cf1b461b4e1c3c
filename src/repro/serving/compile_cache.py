"""AOT compile cache: pay every jit variant's compile cost up front.

The paged engine's device dispatches are deliberately bucketed so that
only O(log) distinct jit variants can ever exist: decode / verify / spec
bursts specialize on the pow-2 live page-table width
(`_live_table_width`), prefill on the pow-2 suffix-chunk bucket
(`_bucket_width`) x the shared-prefix skip, and the speculative q_len is
static (draft_len + 1, padded). That discipline makes the variant set
*enumerable*: this module walks it, `lower()`s and `compile()`s each
variant ahead of time (JAX AOT), and installs the compiled executables in
the engine's dispatch table (`PagedServingEngine._exec`) so the serving
hot path never hits a tracing pause.

Why it matters for the clock: a lazily-jitted engine smears compilation
across the first seconds of a trace — exactly the window TTFT and
tokens/sec are measured over — and a mid-trace width-bucket crossing
stalls every live request behind a compile. After `warmup(engine)`:

  * every dispatch the run loop can issue hits a pre-compiled executable;
  * `stats["perf"]["post_warmup_variants"]` counts any variant first seen
    *after* warmup — the perf-smoke CI job asserts it stays ZERO, which
    pins the bucketing discipline itself (a new dynamic shape sneaking
    into the hot path shows up as a nonzero counter, not as a mysterious
    latency spike);
  * `stats["perf"]["jit_variants_compiled"]` / `compile_wall_s` /
    `warmup_wall_s` report how many variants exist and what they cost.

Shapes are described with `jax.ShapeDtypeStruct` — warmup never runs the
model, touches the pool, or consumes RNG; it only compiles.

Mesh engines (SchedulerConfig.mesh set) take a different route: their
step functions are `jit(shard_map(...))`, and AOT-compiled executables
are brittle about input shardings there, so instead of installing
`_exec` entries, `_mesh_warmup` primes the LAZY jit cache by CALLING
every variant once with the engine's real pools (donated and reassigned,
values untouched: bursts run zero steps, masked writes land only on the
reserved trash page 0) and all-False active masks. Dispatch then falls
through `_exec` to the warm `fn(*args)` path; `_compiled_keys` is
pre-populated either way, so `post_warmup_variants` stays zero on both
routes.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def _sds(tree):
    """ShapeDtypeStruct skeleton of a pytree of arrays (AOT lowering input)."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a)),
        tree)


def table_width_buckets(engine) -> list[int]:
    """Every value `_live_table_width` can return: powers of two clamped
    to max_pages (plus max_pages itself when it is not a power of two)."""
    out, mp = [], 1
    while mp < engine.sched.max_pages:
        out.append(mp)
        mp *= 2
    out.append(engine.sched.max_pages)
    return sorted(set(out))


def prefill_width_buckets(engine) -> list[int]:
    """Every suffix width `_bucket_width` can return for an admittable
    request: pow-2 chunk counts clamped at the engine's token capacity."""
    chunk = engine.sched.prefill_chunk
    cap_chunks = max(1, (engine.sched.max_pages * engine.sched.page_size)
                     // chunk)
    out, b = [], 1
    while b < cap_chunks:
        out.append(b * chunk)
        b *= 2
    out.append(cap_chunks * chunk)
    return sorted(set(out))


def state_width_buckets(engine) -> list[int]:
    """Every prompt width `_state_width` can return for an admittable
    request on a state-slot family: powers of two clamped at the token
    capacity (plus the capacity itself when it is not a power of two)."""
    cap = engine.sched.max_pages * engine.sched.page_size
    out, w = [], 1
    while w < cap:
        out.append(w)
        w *= 2
    out.append(cap)
    return sorted(set(out))


def enumerate_variants(engine, skips=(0,)) -> list[tuple]:
    """The (key, jit_fn, abstract_args) list `warmup` compiles.

    `skips`: shared-prefix token counts to pre-build prefill variants for
    (0 = the cold path every mode uses). Prefix-"share" traces admit with
    data-dependent skips; pass the chunk multiples your trace can hit to
    pre-compile those too, or accept lazy compiles on first prefix hit.
    """
    sched, cfg = engine.sched, engine.cfg
    s = sched.num_slots
    params = _sds(engine.params)
    key = _sds(jax.random.PRNGKey(0))
    i32 = jnp.int32
    vec = jax.ShapeDtypeStruct((s,), i32)
    mask = jax.ShapeDtypeStruct((s,), jnp.bool_)
    scalar = jax.ShapeDtypeStruct((), i32)
    out = []
    if engine.family.state_slots:
        # state-slot families (serving/statecache.py): the burst decode
        # threads the packed state store, and admission prefill is the
        # per-pow-2-prompt-width `_sprefill_fn` family (no chunked
        # prefill, no prefix loads, no speculate/tiered variants —
        # families.py rejects those scheduler modes up front)
        packed = _sds(engine.states)
        if engine.family.paged_kv:  # hybrid: pages ride along
            pk, pv = _sds(engine.pool.k), _sds(engine.pool.v)
            for mp in table_width_buckets(engine):
                table = jax.ShapeDtypeStruct((s, mp), i32)
                out.append((("decode", mp), engine._decode_fn,
                            (params, pk, pv, table, vec, mask, vec, vec,
                             scalar, key, packed)))
            full = jax.ShapeDtypeStruct((s, sched.max_pages), i32)
            for width in state_width_buckets(engine):
                toks = jax.ShapeDtypeStruct((width,), i32)
                skey, fn = engine._sprefill_fn(width)
                out.append((skey, fn,
                            (params, toks, scalar, scalar, pk, pv, full,
                             vec, packed, key)))
        else:  # pure-recurrent (xlstm): no pages at all
            out.append((("decode", 0), engine._decode_fn,
                        (params, mask, vec, vec, scalar, key, packed)))
            for width in state_width_buckets(engine):
                toks = jax.ShapeDtypeStruct((width,), i32)
                skey, fn = engine._sprefill_fn(width)
                out.append((skey, fn,
                            (params, toks, scalar, scalar, packed, key)))
        return out
    pk, pv = _sds(engine.pool.k), _sds(engine.pool.v)
    for mp in table_width_buckets(engine):
        table = jax.ShapeDtypeStruct((s, mp), i32)
        if sched.speculate and sched.spec_device:
            ctx = jax.ShapeDtypeStruct(engine.ctx_buf.shape, i32)
            out.append((("spec", mp), engine._spec_fn,
                        (params, pk, pv, table, vec, mask, mask, ctx, vec,
                         vec, scalar)))
        elif sched.speculate:
            fed = jax.ShapeDtypeStruct((s, sched.draft_len + 1), i32)
            out.append((("verify", mp), engine._verify_fn,
                        (params, pk, pv, table, vec, mask, mask, fed, vec)))
        elif engine.backend2 is not None:
            # tiered decode (DegradeConfig on): both pools + tables ride
            # the dispatch, a (s,) tier mask routes each slot
            pk2, pv2 = _sds(engine.pool2.k), _sds(engine.pool2.v)
            out.append((("decode", mp), engine._decode_fn,
                        (params, pk, pv, pk2, pv2, table, table, mask,
                         vec, mask, mask, vec, vec, scalar, key)))
        else:
            out.append((("decode", mp), engine._decode_fn,
                        (params, pk, pv, table, vec, mask, mask, vec, vec,
                         scalar, key)))
    chunk = sched.prefill_chunk
    for skip in sorted(set(skips)):
        if skip % chunk:
            raise ValueError(
                f"skip {skip} is not a multiple of prefill_chunk {chunk}")
        if skip:
            n = skip // sched.page_size
            out.append((("prefix_load", n), engine._prefix_load_fn(n),
                        (jax.ShapeDtypeStruct((n,), i32), pk, pv)))
        pfx = jax.ShapeDtypeStruct(
            (cfg.num_layers, 1, skip, cfg.num_kv_heads, cfg.head_dim),
            jnp.dtype(cfg.compute_dtype))
        for width in prefill_width_buckets(engine):
            nc = width // chunk
            toks = jax.ShapeDtypeStruct((nc, chunk), i32)
            grp = jax.ShapeDtypeStruct((nc, chunk // sched.page_size), i32)
            out.append((("prefill", width, skip),
                        engine._prefill_fn(width, skip),
                        (params, toks, grp, scalar, scalar, pfx, pfx, key,
                         pk, pv)))
    return out


def _mesh_warmup(engine, skips=(0,)) -> dict:
    """Warm a mesh engine by harmless real calls — see the module
    docstring. Pool arguments are the engine's live pools: they are
    donated through each call and reassigned from the outputs, and the
    calls cannot alter pool *data* (decode/spec bursts run k=0 steps;
    verify/prefill run with inactive slots and all-zero page tables, so
    every masked write lands on trash page 0, which holds no data by
    contract)."""
    t_start = time.perf_counter()
    # state-slot families never run under a mesh (families.py rejects
    # sched.mesh at construction), so no state-cache variant kind can
    # reach this path
    assert not engine.family.state_slots, \
        "state-cache variants cannot run under a mesh"
    sched, cfg = engine.sched, engine.cfg
    s = sched.num_slots
    i32 = jnp.int32
    zvec = jnp.zeros((s,), i32)
    fmask = jnp.zeros((s,), jnp.bool_)
    zscalar = jnp.zeros((), i32)
    rng = jax.random.PRNGKey(0)
    compile_wall = 0.0
    new = 0
    for vkey, fn, _ in enumerate_variants(engine, skips=skips):
        if vkey in engine._compiled_keys:
            continue
        t0 = time.perf_counter()
        kind = vkey[0]
        # the same mesh-committed argument placement `_dispatch` uses, so
        # the warm jit cache entries are the ones the run loop hits
        call = lambda *a, fn=fn: fn(*engine._on_mesh(a))  # noqa: E731
        if kind == "spec":
            mp = vkey[1]
            table = jnp.zeros((s, mp), i32)
            ctx = jnp.zeros(engine.ctx_buf.shape, i32)
            o = call(engine.params, engine.pool.k, engine.pool.v, table,
                   zvec, fmask, fmask, ctx, zvec, zvec, zscalar)
            engine.pool = engine.pool._replace(k=o[0], v=o[1])
        elif kind == "verify":
            mp = vkey[1]
            table = jnp.zeros((s, mp), i32)
            fed = jnp.zeros((s, sched.draft_len + 1), i32)
            o = call(engine.params, engine.pool.k, engine.pool.v, table,
                   zvec, fmask, fmask, fed, zvec)
            engine.pool = engine.pool._replace(k=o[0], v=o[1])
        elif kind == "decode" and engine.backend2 is not None:
            mp = vkey[1]
            table = jnp.zeros((s, mp), i32)
            o = call(engine.params, engine.pool.k, engine.pool.v,
                   engine.pool2.k, engine.pool2.v, table, table, fmask,
                   zvec, fmask, fmask, zvec, zvec, zscalar, rng)
            engine.pool = engine.pool._replace(k=o[0], v=o[1])
            engine.pool2 = engine.pool2._replace(k=o[2], v=o[3])
        elif kind == "decode":
            mp = vkey[1]
            table = jnp.zeros((s, mp), i32)
            o = call(engine.params, engine.pool.k, engine.pool.v, table,
                   zvec, fmask, fmask, zvec, zvec, zscalar, rng)
            engine.pool = engine.pool._replace(k=o[0], v=o[1])
        elif kind == "prefix_load":
            n = vkey[1]
            call(jnp.zeros((n,), i32), engine.pool.k, engine.pool.v)
        elif kind == "prefill":
            width, skip = vkey[1], vkey[2]
            nc = width // sched.prefill_chunk
            toks = jnp.zeros((nc, sched.prefill_chunk), i32)
            grp = jnp.zeros((nc, sched.prefill_chunk // sched.page_size),
                            i32)
            pfx = jnp.zeros(
                (cfg.num_layers, 1, skip, cfg.num_kv_heads, cfg.head_dim),
                jnp.dtype(cfg.compute_dtype))
            o = call(engine.params, toks, grp, zscalar, zscalar, pfx, pfx,
                   rng, engine.pool.k, engine.pool.v)
            engine.pool = engine.pool._replace(k=o[1], v=o[2])
        else:  # pragma: no cover — enumerate_variants defines the kinds
            raise AssertionError(f"unknown warmup variant {vkey}")
        jax.block_until_ready(engine.pool.k)
        compile_wall += time.perf_counter() - t0
        new += 1
        engine._compiled_keys.add(vkey)
        engine._perf["jit_variants_compiled"] += 1
    engine._perf["compile_wall_s"] += compile_wall
    engine._perf["warmup_wall_s"] += time.perf_counter() - t_start
    engine._warmed = True
    return {
        "variants": len(engine._compiled_keys),
        "new_variants": new,
        "compile_wall_s": compile_wall,
        "warmup_wall_s": time.perf_counter() - t_start,
        "keys": sorted(engine._compiled_keys),
    }


def warmup(engine, skips=(0,)) -> dict:
    """AOT-compile every enumerable dispatch variant into the engine.

    After this returns, `engine` is *warmed*: its run loop dispatches
    through pre-compiled executables, and any variant compiled later
    increments `stats["perf"]["post_warmup_variants"]` (the regression
    counter CI pins at zero). Idempotent; returns a stats dict:

      variants        — total variants now installed
      new_variants    — variants this call compiled (0 when already warm)
      compile_wall_s  — seconds spent inside lower()+compile()
      warmup_wall_s   — total wall of this call (enumeration included)
      keys            — the installed variant keys
    """
    if getattr(engine, "_shard", None) is not None:
        return _mesh_warmup(engine, skips=skips)
    t_start = time.perf_counter()
    compile_wall = 0.0
    new = 0
    for vkey, fn, args in enumerate_variants(engine, skips=skips):
        if vkey in engine._exec:
            continue
        t0 = time.perf_counter()
        engine._exec[vkey] = fn.lower(*args).compile()
        compile_wall += time.perf_counter() - t0
        new += 1
        if vkey not in engine._compiled_keys:
            engine._compiled_keys.add(vkey)
            engine._perf["jit_variants_compiled"] += 1
    engine._perf["compile_wall_s"] += compile_wall
    engine._perf["warmup_wall_s"] += time.perf_counter() - t_start
    engine._warmed = True
    return {
        "variants": len(engine._exec),
        "new_variants": new,
        "compile_wall_s": compile_wall,
        "warmup_wall_s": time.perf_counter() - t_start,
        "keys": sorted(engine._exec),
    }
