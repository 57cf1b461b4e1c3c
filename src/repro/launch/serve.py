"""Serving launcher: prefill a (possibly ragged) batch of prompts, decode
through a pluggable attention backend, report memory/compression stats.

    PYTHONPATH=src python -m repro.launch.serve --arch mistral-7b --reduced \
        --prompt-len 64 --gen 32 --backend quant-pallas --interpret

quant-pallas compiles its Pallas kernels for the TPU unless --interpret
runs them in the interpreter (the way to use it on a CPU); it never picks
between the two by itself.

Ragged batches: --prompt-lens 64,48,32,20 gives each row its own prompt
length (right-padded internally); per-sequence EOS (--eos-id) stops rows
independently and the whole loop exits early once every row is done.

Paged continuous batching: --paged serves the same prompts through the
page-pool scheduler (`repro.serving.scheduler`) — requests are admitted into
decode slots mid-flight, evicted on EOS/budget with their pages freed
immediately, and per-request latency/throughput stats are reported.
Requires a quantized backend and a window-less config (e.g. qwen3-0.6b).

Prefix caching: --paged --prefix-cache share --shared-prefix 256 gives every
prompt a common 256-token "system prompt"; the first request prefills it
once, and every later request maps those packed pages by reference
(copy-on-write, refcount-tracked) and prefills only its own suffix.

Speculative decoding: --paged --speculate --draft-len 4 switches the decode
loop to draft-verify-rollback (`repro.serving.speculate`): each dispatch
scores the pending token plus up to 4 prompt-lookup drafts at once, commits
the accepted run, and rolls back the rest. Greedy tokens are bitwise
identical to the plain path; acceptance/steps-per-token stats are printed.

Paged serving AOT-warms by default (`engine.warmup()` compiles every
enumerable jit variant before the first request — serving/compile_cache.py)
and prints the dispatch-discipline counters: jit variants compiled, compile
and warmup wall, variants compiled post-warmup (must be 0), and host syncs
engine-wide plus per request. --no-warmup shows the lazy alternative.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache import kvcache
from repro.configs import registry
from repro.launch import jax_cache
from repro.launch import steps as steps_lib
from repro.models import transformer
from repro.serving import backends as backends_lib
from repro.serving import engine
from repro.serving import families as families_lib
from repro.serving import pages as pages_lib
from repro.serving import scheduler as scheduler_lib
from repro.serving import server as server_lib
from repro.serving import statecache as statecache_lib
from repro.serving import telemetry as telemetry_lib


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list(registry.ALL_IDS))
    ap.add_argument("--model", default=None, choices=list(registry.ALL_IDS),
                    help="alias of --arch (the registry id to serve); "
                         "families beyond dense decoders route through "
                         "their adapter (serving/families.py) — "
                         "unsupported combinations fail with a typed "
                         "UnsupportedFamilyError naming the missing "
                         "capability")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--prompt-lens", type=str, default=None,
                    help="comma-separated per-sequence prompt lengths "
                         "(overrides --batch/--prompt-len; ragged batch)")
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--backend", default="auto",
                    choices=("auto",) + backends_lib.BACKEND_NAMES)
    ap.add_argument("--interpret", action="store_true",
                    help="quant-pallas: run the Pallas kernels in the "
                         "interpreter (CPU). Without it the kernels are "
                         "compiled, which needs a TPU")
    ap.add_argument("--no-quant", action="store_true",
                    help="shorthand for --backend raw")
    ap.add_argument("--storage", default="auto",
                    choices=("auto", "uint8", "bitpack"),
                    help="quantized cache representation (auto -> bitpack "
                         "word streams; uint8 keeps one container per code)")
    ap.add_argument("--paged", action="store_true",
                    help="serve through the paged continuous-batching "
                         "scheduler instead of the static batch engine")
    ap.add_argument("--slots", type=int, default=2,
                    help="paged: concurrent decode slots")
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged: tokens per physical page")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="paged: pool size (0 -> sized to the trace)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="paged: tokens per chunked-prefill call "
                         "(multiple of --page-size)")
    ap.add_argument("--prefix-cache", default="off",
                    choices=scheduler_lib.PREFIX_MODES,
                    help="paged: copy-on-write prefix caching. 'share' "
                         "maps already-served prompt prefixes into new "
                         "requests' page tables and prefills only the "
                         "suffix; 'cold' uses the same prefill numerics "
                         "without sharing (the parity baseline); 'off' "
                         "matches the static engine bit-for-bit")
    ap.add_argument("--prefix-pages", type=int, default=128,
                    help="paged: LRU bound on pages the prefix trie may "
                         "pin (only with --prefix-cache share)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many common random tokens to every "
                         "prompt (a synthetic system prompt, to exercise "
                         "--prefix-cache share)")
    ap.add_argument("--speculate", action="store_true",
                    help="paged: speculative draft-verify-rollback "
                         "decoding (prompt-lookup self-drafting; greedy "
                         "only — tokens stay bitwise identical, but "
                         "repeated structure costs fewer sequential "
                         "forward passes)")
    ap.add_argument("--draft-len", type=int, default=4,
                    help="paged: draft tokens per verify step "
                         "(with --speculate)")
    ap.add_argument("--draft-max-ngram", type=int, default=3,
                    help="paged: longest trailing n-gram the drafter "
                         "matches (with --speculate)")
    ap.add_argument("--preempt", action="store_true",
                    help="paged: SLO-aware admission — higher-priority "
                         "arrivals preempt lower-priority requests by "
                         "spilling their packed pages to host memory "
                         "(restored bitwise-losslessly when capacity "
                         "frees); see docs/serving.md pressure ladder")
    ap.add_argument("--priorities", type=str, default=None,
                    help="paged: comma-separated per-request priorities "
                         "(cycled over the batch; higher preempts lower "
                         "with --preempt). Default: all 0")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="paged: admission deadline for every request — "
                         "a request still queued this many ms after "
                         "arrival is shed with a typed result instead of "
                         "waiting")
    ap.add_argument("--stagger-s", type=float, default=0.0,
                    help="paged: space request arrivals this many seconds "
                         "apart (arrival = rid * stagger; lets later "
                         "high-priority arrivals actually preempt)")
    ap.add_argument("--degrade-pages", type=int, default=0,
                    help="paged: enable tiered-precision degradation with "
                         "a tier-2 pool of this many pages — under page "
                         "pressure a victim is recompressed to a "
                         "lower-bit schedule instead of spilled "
                         "(with --preempt; lossy, recorded per request)")
    ap.add_argument("--degrade-floor-bits", type=float, default=1.0,
                    help="paged: quality floor (mean angle bits/elem) the "
                         "degraded schedule must stay at or above")
    ap.add_argument("--max-wall-s", type=float, default=None,
                    help="paged: wall-clock watchdog — abort a hung trace "
                         "with a diagnostic dump after this many seconds")
    ap.add_argument("--no-warmup", action="store_true",
                    help="paged: skip the AOT warmup (variants then "
                         "compile lazily inside the serve, smearing "
                         "compile wall across the first requests)")
    ap.add_argument("--serve-http", action="store_true",
                    help="paged: serve the batch through the HTTP/SSE "
                         "front-end (serving/server.py) instead of "
                         "calling the engine in-process — each request "
                         "goes over a real socket as POST /generate and "
                         "streams its tokens back as SSE events")
    ap.add_argument("--port", type=int, default=0,
                    help="with --serve-http: TCP port to bind "
                         "(0 = ephemeral; the chosen port is printed)")
    ap.add_argument("--metrics", action="store_true",
                    help="paged: print the metrics registry in Prometheus "
                         "text exposition format after the run (what "
                         "GET /metrics serves)")
    ap.add_argument("--trace-out", type=str, default=None,
                    help="paged: write the telemetry ring buffer as "
                         "Chrome/Perfetto trace_event JSON to this path "
                         "after the run")
    ap.add_argument("--no-telemetry", action="store_true",
                    help="paged: disable the event tracer (metrics "
                         "counters stay on — they are host arithmetic "
                         "and never touch device state)")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="stop a sequence when it samples this token")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 -> greedy")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.arch is None and args.model is None:
        ap.error("one of --arch / --model is required")
    if args.arch and args.model and args.arch != args.model:
        ap.error("--arch and --model disagree (they are aliases)")
    args.arch = args.arch or args.model
    jax_cache.use_persistent_cache()

    run = registry.get_run_config(args.arch)
    cfg = registry.get_reduced_config(args.arch) if args.reduced \
        else run.model
    backend_name = "raw" if args.no_quant else args.backend
    if backend_name == "raw":
        run = dataclasses.replace(
            run, quant=dataclasses.replace(run.quant, enabled=False))
    run = dataclasses.replace(
        run, model=cfg, backend=backend_name,
        quant=dataclasses.replace(run.quant, storage=args.storage))
    qz = steps_lib.make_quantizer(run)
    backend = backends_lib.from_run(run, qz, interpret=args.interpret)

    if args.prompt_lens:
        lens = [int(x) for x in args.prompt_lens.split(",")]
    else:
        lens = [args.prompt_len] * args.batch
    if args.shared_prefix:
        lens = [n + args.shared_prefix for n in lens]
    batch, s_max = len(lens), max(lens)
    prompt_lengths = jnp.asarray(lens, jnp.int32)

    params, _ = transformer.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(args.seed)
    shared = rng.integers(0, cfg.vocab_size, args.shared_prefix)
    tokens = np.zeros((batch, s_max), np.int32)
    for i, n in enumerate(lens):
        tokens[i, :args.shared_prefix] = shared
        tokens[i, args.shared_prefix:n] = rng.integers(
            0, cfg.vocab_size, n - args.shared_prefix)
    prompts = jnp.asarray(tokens)

    if args.paged:
        state_cache = statecache_lib.state_cache_config_from_quant(
            run.quant, raw=backend_name == "raw")
        try:
            return _serve_paged(args, cfg, qz, backend, params, tokens,
                                lens, state_cache)
        except families_lib.UnsupportedFamilyError as e:
            print(f"unsupported: {e}")
            return 2

    result = engine.generate(
        params, cfg, backend, prompts, prompt_lengths,
        max_new_tokens=args.gen,
        sampling=engine.SamplingConfig(
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p),
        eos_id=args.eos_id,
        rng=jax.random.PRNGKey(args.seed),
    )

    out = np.asarray(result.tokens)
    num = np.asarray(result.num_generated)
    print(f"backend: {backend.name}; decode steps run: {int(result.steps)} "
          f"/ {args.gen}")
    for i in range(batch):
        print(f"  seq {i}: prompt {lens[i]:4d} tok -> generated "
              f"{int(num[i]):3d} tok: {out[i, :min(int(num[i]), 12)]}")

    if result.cache is not None and cfg.has_kv_cache:
        total = s_max + args.gen
        nbytes = kvcache.cache_physical_bytes(result.cache)
        raw = jax.eval_shape(
            lambda: kvcache.init_raw_cache(cfg, batch, total, jnp.bfloat16))
        raw_bytes = kvcache.cache_physical_bytes(raw)
        print(f"cache bytes: {nbytes/1e6:.2f} MB "
              f"(bf16 reference: {raw_bytes/1e6:.2f} MB, "
              f"{raw_bytes/max(nbytes,1):.2f}x compression)")
        if qz is not None:
            print(f"rates: angle {qz.config.angle_bits():.2f} b/elem, "
                  f"end-to-end {qz.config.total_bits():.2f} b/elem "
                  f"(physical {qz.config.physical_bits():.2f})")
    return 0


def _serve_paged(args, cfg, qz, backend, params, tokens, lens,
                 state_cache=None):
    """Run the prompt set through the continuous-batching scheduler."""
    prios = ([int(x) for x in args.priorities.split(",")]
             if args.priorities else [0])
    requests = [
        scheduler_lib.Request(rid=i, tokens=tokens[i, :n].astype(np.int32),
                              max_new_tokens=args.gen,
                              arrival=i * args.stagger_s,
                              priority=prios[i % len(prios)],
                              deadline_ms=args.deadline_ms)
        for i, n in enumerate(lens)
    ]
    chunk = args.prefill_chunk
    max_context = -(-max(lens) // chunk) * chunk + args.gen
    num_pages = args.num_pages
    if num_pages <= 0:
        per_req = pages_lib.pages_for_tokens(
            -(-max(lens) // chunk) * chunk + args.gen, args.page_size)
        num_pages = 1 + per_req * max(args.slots, 1) * 2
    prefix_pages = args.prefix_pages
    if args.prefix_cache == "share":
        prefix_pages = min(prefix_pages, max(1, (num_pages - 1) // 2))
    sched = scheduler_lib.SchedulerConfig(
        num_slots=args.slots, page_size=args.page_size,
        num_pages=num_pages, max_context=max_context,
        prefill_chunk=chunk, eos_id=args.eos_id,
        sampling=engine.SamplingConfig(
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p),
        prefix_cache=args.prefix_cache, prefix_pages=prefix_pages,
        speculate=args.speculate, draft_len=args.draft_len,
        draft_max_ngram=args.draft_max_ngram,
        preempt=args.preempt,
        degrade=(scheduler_lib.DegradeConfig(
            num_pages=args.degrade_pages,
            floor_angle_bits=args.degrade_floor_bits)
            if args.degrade_pages else None),
        max_wall_s=args.max_wall_s,
        telemetry=not args.no_telemetry)
    eng = scheduler_lib.PagedServingEngine(params, cfg, backend, sched,
                                           state_cache=state_cache)
    if not args.no_warmup:
        eng.warmup()
    if args.serve_http:
        results, stats = _serve_http(args, eng, requests)
    else:
        results, stats = eng.run(requests,
                                 rng=jax.random.PRNGKey(args.seed))
    print(f"backend: {backend.name} (paged); slots={args.slots} "
          f"page_size={args.page_size} pool={num_pages - 1} pages; "
          f"decode steps: {stats['decode_steps']}")
    for r in results:
        flags = "".join(
            [f" [{r.status}]" if r.status != "completed" else "",
             f" prio {r.priority}" if r.priority else "",
             f" preempted x{r.preemptions}" if r.preemptions else "",
             " degraded" if r.degraded else ""])
        print(f"  req {r.rid}: prompt {r.prompt_len:4d} tok -> generated "
              f"{len(r.tokens):3d} tok in {r.latency_s * 1e3:7.1f} ms "
              f"(ttft {r.ttft_s * 1e3:6.1f} ms, {r.host_sync_count} host "
              f"syncs):{flags} {r.tokens[:12]}")
    perf = stats["perf"]
    print(f"dispatch: {perf['jit_variants_compiled']} jit variants "
          f"({'AOT warmup' if perf['warmed'] else 'lazily compiled'}, "
          f"compile wall {perf['compile_wall_s']:.1f} s, warmup wall "
          f"{perf['warmup_wall_s']:.1f} s); "
          f"{perf['post_warmup_variants']} compiled post-warmup "
          f"(0 = every hot-loop shape was enumerated); "
          f"{perf['host_sync_count']} host syncs total "
          f"(one per burst boundary, not per token)")
    print(f"aggregate: {stats['tokens_per_sec']:.1f} tok/s, "
          f"p50 latency {stats['latency_p50_s'] * 1e3:.1f} ms, "
          f"p99 {stats['latency_p99_s'] * 1e3:.1f} ms; prefill "
          f"{stats['prefill_tokens_computed']} tok in "
          f"{stats['prefill_chunks']} chunks")
    # per-run latency distributions, as histogram views over the metrics
    # registry (the same buckets GET /metrics exposes cumulatively)
    print(telemetry_lib.format_histogram(stats["ttft_hist"], "TTFT"))
    print(telemetry_lib.format_histogram(stats["tpot_hist"], "TPOT"))
    if "spec" in stats:
        sp = stats["spec"]
        print(f"speculative: draft_len {sp['draft_len']}; "
              f"{sp['draft_accepted']}/{sp['draft_proposed']} drafts "
              f"accepted ({sp['acceptance_rate']:.0%}); "
              f"{sp['verify_steps']} forward passes for "
              f"{sp['decode_tokens']} decode tokens = "
              f"{sp['steps_per_token']:.2f} steps/token")
    slo = stats["slo"]
    if args.preempt or args.deadline_ms is not None or args.degrade_pages:
        per_class = ", ".join(
            f"prio {p}: n={c['n']} p50 {c['latency_p50_s'] * 1e3:.1f} ms "
            f"p99 {c['latency_p99_s'] * 1e3:.1f} ms"
            for p, c in sorted(slo["per_class"].items()))
        print(f"slo: {slo['completed']} completed, {slo['shed']} shed, "
              f"{slo['cancelled']} cancelled; {slo['spills']} spills "
              f"({slo['spill_bytes'] / 1e6:.2f} MB), {slo['restores']} "
              f"restores ({slo['restore_retries']} retries), "
              f"{slo['degraded']} degraded, {slo['preempted']} requests "
              f"preempted; {per_class}")
    if "prefix" in stats:
        px = stats["prefix"]
        print(f"prefix cache: {px['hits']} hits / {px['misses']} misses, "
              f"{px['hit_tokens']} prompt tokens served from shared pages "
              f"({px['nodes']} pages pinned, bound {px['max_pages']})")
    fam = stats["family"]
    caps = ", ".join(k for k in ("paged_kv", "state_slots", "speculate",
                                 "prefix_share", "degrade", "mesh")
                     if fam[k])
    print(f"family: {fam['name']} ({caps or 'no serving capabilities'})")
    if eng.pool is not None:
        pool_mb = stats["pool_bytes"] / 1e6
        page_kb = pages_lib.page_payload_bytes(qz, cfg, args.page_size) / 1e3
        print(f"pool-resident payload: {pool_mb:.2f} MB "
              f"({page_kb:.2f} kB/page x {stats['pages_total']} pages)")
    if fam["state_slots"]:
        raw = fam["state_raw_bytes_per_slot"]
        per = fam["state_bytes_per_slot"]
        print(f"state cache: {fam['state_cache_bytes'] / 1e3:.2f} kB "
              f"({per / 1e3:.2f} kB/slot vs {raw / 1e3:.2f} kB raw f32, "
              f"{raw / max(per, 1):.2f}x compression; encode wall "
              f"{fam['state_encode_seconds']:.2f} s)")
    if args.metrics:
        print("--- /metrics " + "-" * 51)
        print(eng.telemetry.registry.render_prometheus(), end="")
    if args.trace_out:
        with open(args.trace_out, "w") as f:
            f.write(eng.telemetry.tracer.to_perfetto_json())
        print(f"trace: {len(eng.telemetry.tracer.events())} events -> "
              f"{args.trace_out} (open at https://ui.perfetto.dev)")
    return 0


def _serve_http(args, eng, requests):
    """Serve the request batch through the HTTP/SSE front-end: boot the
    server on the warmed engine, submit every request as POST /generate
    over a real socket, collect the streamed tokens, and shut down.
    Returns (results, stats) shaped like `PagedServingEngine.run`."""
    import concurrent.futures

    fe = server_lib.HTTPFrontend(eng, port=args.port)
    fe.start()
    print(f"http: listening on 127.0.0.1:{fe.port} "
          f"(POST /generate; GET /metrics /trace /healthz)")

    def one(req):
        rid, toks = None, []
        for ev, doc in server_lib.sse_generate(fe.port, {
                "prompt": [int(t) for t in req.tokens],
                "max_new_tokens": req.max_new_tokens,
                "priority": req.priority,
                "deadline_ms": req.deadline_ms}):
            if ev == "tokens":
                toks.extend(doc["tokens"])
            elif ev == "result":
                rid = doc["rid"]
        return rid, toks

    with concurrent.futures.ThreadPoolExecutor(
            max_workers=max(len(requests), 1)) as ex:
        streamed = dict(ex.map(one, requests))
    stats = fe.stop()
    results = fe.results()
    for res in results:
        if streamed.get(res.rid) != [int(t) for t in res.tokens]:
            raise AssertionError(
                f"SSE stream for rid {res.rid} diverged from its typed "
                f"result: {streamed.get(res.rid)} != {list(res.tokens)}")
    return results, stats


if __name__ == "__main__":
    raise SystemExit(main())
