"""One run of one benchmark cell: build the served system from the cell's
files, warm up the shapes its traffic uses, drive the measured window,
read the device, and check what the window served.

The cell's parts are found by name: `configs/<config>.json` (model sizes
and how it is served), `arch/<arch>.py` (everything known of the
architecture that the configuration's `"arch"` key names, `dense_decoder`
where it names none: see `arch/__init__.py`), `traffic/<mix>.json` (the
load and the server's settings for it, read by `traffic.py`),
`cells/<workload>.json` (the correctness limits), `metrics/<metric>.py`
(one reader per metric).
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Optional

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class NotADevice(RuntimeError):
    """A device measurement was asked of a run that had no TPU."""


# ------------------------------------------------------------------ cell --
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    arch: object  # the module arch/<arch>.py of the configuration
    mix: dict
    limits: dict
    end_to_end: list  # BENCHMARK.json metric entries that this cell reports
    per_layer: list


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, spec: Optional[dict] = None) -> Cell:
    spec = spec if spec is not None else _json(ROOT / "BENCHMARK.json")
    w = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if w is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    import arch

    config = _json(HERE / "configs" / f"{w['config']}.json")
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        arch=arch.of(config),
        mix=_json(HERE / "traffic" / f"{w['traffic']}.json"),
        limits=_json(HERE / "cells" / f"{workload}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)])


def reader(metric: str):
    """The `read(window)` function of metrics/<metric>.py."""
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------- served system --
def quantizer(q: dict, layers: list):
    """The program's K/V quantizer for a `serving.kv_quant` block, its
    schedule over `layers`, the (q heads, kv heads, head_dim) of each
    layer whose K/V live in pages (`attention_layers` of the arch)."""
    from repro.configs.base import QuantConfig
    from repro.core.quantizer import KVQuantizer

    qc = QuantConfig(
        schedule=q["schedule"], n_early=min(q["n_early"], len(layers)),
        boost_k=q["boost_k"], boost_v=q["boost_v"], base_k=q["base_k"],
        base_v=q["base_v"], k_norm_bits=q["k_norm_bits"],
        k_norm_log=q["k_norm_log"], v_norm_bits=q["v_norm_bits"],
        v_norm_log=q["v_norm_log"], seed=q["rotation_seed"],
        storage=q["storage"])
    return KVQuantizer(qc.build(layers[0][2], len(layers)))


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


@dataclasses.dataclass(frozen=True)
class Sizes:
    max_context: int
    num_pages: int
    prefix_pages: int


def sizes(mix: dict, items, docs) -> Sizes:
    """Page table, pool and trie bound for the cell's requests. The pool
    is the mix's `pool_pages` where it sets a memory budget (admission
    then waits for pages as a deployment's would); otherwise it holds the
    pinned documents, one prompt's blocks in the trie, and every slot's
    largest fresh span."""
    ps, chunk, slots = mix["page_size"], mix["prefill_chunk"], mix["slots"]
    max_context, fresh = 0, 0
    for it in items:
        plen = len(it.tokens)
        span = max(_cdiv(plen, chunk) * chunk, plen + it.max_new_tokens)
        max_context = max(max_context, span)
        fresh = max(fresh, _cdiv(span, ps) - it.shared // ps)
    prefix_pages = 0
    if mix["prefix_cache"] == "share":  # the documents, and one prompt
        prefix_pages = sum(len(d) // ps for d in docs) \
            + _cdiv(mix["prompt"]["hi"], ps)
    pages = mix.get("pool_pages",
                    1 + prefix_pages + slots * fresh + slots)
    return Sizes(max_context, pages, max(prefix_pages, 1))


def scheduler_config(mix: dict, sz: Sizes):
    from repro.serving import scheduler

    return scheduler.SchedulerConfig(
        num_slots=mix["slots"], page_size=mix["page_size"],
        num_pages=sz.num_pages, max_context=sz.max_context,
        prefill_chunk=mix["prefill_chunk"], max_burst=mix["max_burst"],
        prefix_cache=mix["prefix_cache"], prefix_pages=sz.prefix_pages,
        telemetry=False)


def make_weights(seed: int, cfg, init_leaf):
    """Every weight of the served model from `seed`, made on the device in
    one jitted call, in the dtype it is served in: leaf i of the parameter
    tree is `init_leaf(path, shape, z)` (see `arch/__init__.py`) of the
    standard-normal draw z from `fold_in(key, i)`."""
    import jax
    import jax.numpy as jnp

    from repro.models import transformer

    shapes, _ = transformer.abstract_params(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = []
        for i, (path, sds) in enumerate(flat):
            name = "/".join(str(getattr(k, "key", k)) for k in path)
            z = jax.random.normal(jax.random.fold_in(key, i), sds.shape,
                                  jnp.float32)
            out.append(init_leaf(name, sds.shape, z).astype(sds.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    state = np.random.SeedSequence(int(seed) % 2**64).generate_state(2)
    key = jax.random.wrap_key_data(jnp.asarray(state, jnp.uint32))
    return jax.block_until_ready(jax.jit(build)(key))


# ------------------------------------------------------------- warm-up --
def decode_width(engine, n_tokens: int) -> int:
    """The page-table width a burst whose longest slot reaches `n_tokens`
    dispatches at: the least of the engine's own width buckets that holds
    its pages."""
    from repro.serving import compile_cache, pages

    need = pages.pages_for_tokens(n_tokens, engine.sched.page_size)
    return min(w for w in compile_cache.table_width_buckets(engine)
               if w >= need)


def reachable(mix: dict, items, engine, arch) -> tuple[dict, dict]:
    """The dispatch shapes the window's requests can reach: prefill keys
    and decode page-table widths, each with a request that reaches it
    (and for a width, the decode steps it takes that request to get
    there)."""
    from repro.serving import prefix as prefix_lib

    ps, chunk = engine.sched.page_size, engine.sched.prefill_chunk
    need_pf, need_w = {}, {}
    for it in items:
        plen = len(it.tokens)
        skip = 0
        if mix["prefix_cache"] == "share" and it.shared:
            skip = prefix_lib.usable_prefix_tokens(it.shared, plen, chunk)
        need_pf.setdefault(arch.prefill_key(engine, plen, skip), it)
        for n in range(plen + 1, plen + it.max_new_tokens + 1, ps):
            need_w.setdefault(decode_width(engine, n), (it, n - plen))
        w = decode_width(engine, plen + it.max_new_tokens)
        need_w.setdefault(w, (it, it.max_new_tokens))
    return need_pf, need_w


def warm_plan(mix: dict, items, engine, arch, vocab: int,
              seed: int) -> list:
    """Solo requests, one per engine run, that between them dispatch every
    prefill variant and every decode width the window's requests can
    reach. Each copies a window request's shared prefix and lengths, with
    fresh token ids after the prefix."""
    need_pf, need_w = reachable(mix, items, engine, arch)
    rng = np.random.default_rng([int(seed) % 2**64, 2])
    plan, covered = [], set()

    def add(it, gen):
        fresh = rng.integers(0, vocab, len(it.tokens) - it.shared,
                             dtype=np.int32)
        plan.append((np.concatenate([it.tokens[:it.shared], fresh]),
                     int(gen) + 1))
        n, left = len(it.tokens), int(gen)  # the engine's bursts
        while left > 0:
            k = min(mix["max_burst"], left)
            n, left = n + k, left - k
            covered.add(decode_width(engine, n))

    for key, it in sorted(need_pf.items()):
        add(it, 1)
    for w, (it, steps) in sorted(need_w.items()):
        if w not in covered:
            add(it, steps)
    return plan


# -------------------------------------------------------------- window --
@dataclasses.dataclass
class Window:
    """What one measured window gave; the metric readers read this."""

    seconds: float
    out_tokens: int  # emitted inside the window
    attempted: int
    failed: int
    completed: list  # RequestResults completed inside the window
    results: list  # every RequestResult of the window's engine run
    stats: dict
    compiles: int  # programs compiled or loaded inside the window
    decode_tokens: int  # over the whole engine run (window + drain)
    decode_contexts: int
    prefills: list  # (prompt_len, cached prefix) of each admission
    model: dict
    quant: dict
    peaks: dict
    setup_s: float = 0.0
    memory_peak: int = 0
    memory_limit: int = 0
    trace: object = None  # trace_reduce.DeviceTrace with --trace 1
    trace_s: float = 0.0  # length of the traced span (host clock)


class _CompileCounter:
    """Counts XLA compilations and persistent-cache loads while on."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **kw):
        if self.on and event in self.EVENTS:
            self.n += 1


class _GcPauses:
    """Python garbage collections while on: (offset from `t0`, seconds,
    generation) of each, so a stall of the host can be told from one."""

    def __init__(self):
        self.on, self.t0, self.seen, self._start = False, 0.0, [], None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._start = now
        elif self.on and self._start is not None:
            self.seen.append((self._start - self.t0, now - self._start,
                              info["generation"]))

    def summary(self) -> str:
        if not self.seen:
            return "gc: none in the window"
        at, longest, gen = max(self.seen, key=lambda x: x[1])
        return (f"gc: {len(self.seen)} collections in the window, "
                f"{sum(x[1] for x in self.seen):.3f} s in all, longest "
                f"{longest:.3f} s (generation {gen}) at +{at:.1f} s")


def drive_window(engine, items, seconds: float, counter,
                 trace_dir: Optional[Path] = None):
    """Serve `items` through `engine.run` for `seconds`; at the close every
    request still queued or in flight is cancelled from the per-tick
    intake hook. Returns (results, stats, tallies)."""
    import jax

    from repro.serving import scheduler

    reqs = [scheduler.Request(rid=it.rid, tokens=it.tokens,
                              max_new_tokens=it.max_new_tokens,
                              arrival=it.arrival) for it in items]
    plen = {it.rid: len(it.tokens) for it in items}
    shared = {it.rid: it.shared for it in items}
    emitted: dict = {}
    done_at: dict = {}
    tally = dict(out_tokens=0, decode_tokens=0, decode_contexts=0,
                 prefills=[], admitted_in_window=0)
    t = {"end": float("inf"), "closed": False}

    def on_tokens(rid, toks):
        now = time.perf_counter()
        g = emitted.get(rid, 0)
        if g == 0:
            cached = shared[rid] if engine.sched.prefix_cache == "share" \
                else 0
            tally["prefills"].append((plen[rid], cached))
            if now <= t["end"]:
                tally["admitted_in_window"] += 1
        else:
            n = len(toks)
            tally["decode_tokens"] += n
            tally["decode_contexts"] += n * (plen[rid] + g) + n * (n - 1) // 2
        if now <= t["end"]:
            tally["out_tokens"] += len(toks)
        emitted[rid] = g + len(toks)

    def on_result(r):
        done_at[r.rid] = time.perf_counter()

    def intake():
        if not t["closed"] and time.perf_counter() >= t["end"]:
            t["closed"] = True
            for r in reqs:
                if r.rid not in done_at:
                    engine.cancel(r.rid)
        return []

    engine.on_tokens, engine.on_result = on_tokens, on_result
    if trace_dir is not None:
        jax.profiler.start_trace(str(trace_dir))
    pauses = _GcPauses()
    counter.on = pauses.on = True
    t0 = pauses.t0 = time.perf_counter()
    t["end"] = t0 + seconds
    results, stats = engine.run(reqs, rng=jax.random.PRNGKey(0),
                                intake=intake)
    t_run = time.perf_counter() - t0
    counter.on = pauses.on = False
    gc.callbacks.remove(pauses._cb)
    if trace_dir is not None:
        jax.profiler.stop_trace()
    engine.on_tokens = engine.on_result = None
    tally.update(t0=t0, t_end=t["end"], t_run=t_run, done_at=done_at,
                 gc=pauses.summary())
    return results, stats, tally


# ----------------------------------------------------------------- run --
@dataclasses.dataclass
class Outcome:
    window: Window
    checks: dict  # name -> {"value", "limit"}; correct iff every value <= limit
    device: dict
    setup_split: dict
    sample: list  # rids the correctness check compared


@dataclasses.dataclass
class Served:
    """The served system of one run, set up and warm."""

    engine: object
    params: object
    traffic: object
    split: dict  # set-up seconds by part
    counter: _CompileCounter
    peaks: dict


def setup(cell: Cell, seed: int, seconds: float, *,
          interpret: bool = False, t_start: Optional[float] = None,
          ) -> Served:
    """Weights, engine, documents and warm-up of one run of `cell`."""
    t_start = time.perf_counter() if t_start is None else t_start
    import jax

    from repro.launch import jax_cache
    from repro.serving import backends, scheduler

    import peaks as peaks_lib
    import traffic as traffic_lib

    kind = jax.devices()[0].device_kind
    pk = peaks_lib.PEAKS.get(kind, {}) if interpret \
        else peaks_lib.peaks(kind)
    jax_cache.use_persistent_cache()
    jax.config.update("jax_default_matmul_precision",
                      cell.config["serving"]["matmul_precision"])
    counter = _CompileCounter()
    split = {"process_start_s": time.perf_counter() - t_start}

    cfg = cell.arch.model_config(cell.config)
    qz = quantizer(cell.config["serving"]["kv_quant"],
                   cell.arch.attention_layers(cell.config))
    tr = traffic_lib.generate(cell.mix, cfg.vocab_size, seed, seconds)
    sched = scheduler_config(cell.mix, sizes(cell.mix, tr.window,
                                             tr.documents))
    t = time.perf_counter()
    params = make_weights(seed, cfg, cell.arch.init_leaf)
    split["weights_s"] = time.perf_counter() - t
    backend = backends.QuantPallasBackend(cfg, qz, interpret=interpret)
    engine = scheduler.PagedServingEngine(params, cfg, backend, sched)

    t = time.perf_counter()
    if tr.documents:  # the shared prefixes, pinned by the prefix trie
        engine.run([scheduler.Request(rid=-1 - i, tokens=d, max_new_tokens=1)
                    for i, d in enumerate(tr.documents)])
    split["documents_s"] = time.perf_counter() - t
    t = time.perf_counter()
    plan = warm_plan(cell.mix, tr.window, engine, cell.arch, cfg.vocab_size,
                     seed)
    for i, (toks, gen) in enumerate(plan):
        engine.run([scheduler.Request(rid=-100 - i, tokens=toks,
                                      max_new_tokens=gen)])
    split["warmup_s"] = time.perf_counter() - t
    split["warmup_requests"] = len(plan)
    return Served(engine, params, tr, split, counter, pk)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             interpret: bool = False, t_process: Optional[float] = None,
             log=print, control: bool = False) -> Outcome:
    """Set up, measure and check one run of `cell`. `interpret` runs the
    Pallas kernels in the interpreter (CPU rehearsals only; such a run is
    never a device measurement). `control` also reads the control's gap
    (check.py) into `checks["control_gap"]`; the benchmark's runs never
    do."""
    t_start = t_process if t_process is not None else time.perf_counter()
    import jax

    import trace_reduce

    sv = setup(cell, seed, seconds, interpret=interpret, t_start=t_start)
    tr = sv.traffic
    trace_dir = None
    if trace:
        trace_dir = HERE / "_trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
    setup_s = time.perf_counter() - t_start
    results, stats, tally = drive_window(sv.engine, tr.window, seconds,
                                         sv.counter, trace_dir)
    t_end = tally["t_end"]
    completed = [r for r in results if r.status == "completed"
                 and tally["done_at"].get(r.rid, float("inf")) <= t_end]
    arrived = sum(1 for it in tr.window if it.arrival <= seconds) \
        if cell.mix["loop"] == "open" else tally["admitted_in_window"]
    devices = jax.devices()
    dev = devices[0]
    mem = dev.memory_stats() or {}
    w = Window(
        seconds=seconds, out_tokens=tally["out_tokens"], attempted=arrived,
        failed=sum(1 for r in results if r.status == "shed"),
        completed=completed, results=results, stats=stats,
        compiles=sv.counter.n, decode_tokens=tally["decode_tokens"],
        decode_contexts=tally["decode_contexts"],
        prefills=tally["prefills"], model=cell.config,
        quant=cell.config["serving"]["kv_quant"], peaks=sv.peaks,
        setup_s=setup_s, memory_peak=int(mem.get("peak_bytes_in_use", 0)),
        memory_limit=int(mem.get("bytes_limit", 0)))
    if trace:
        w.trace = trace_reduce.reduce_file(
            trace_reduce.find_xplane(str(trace_dir)))
        w.trace_s = tally["t_run"]
        shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"window: {tally['out_tokens']} tokens in {seconds} s, "
        f"{len(completed)} completed, {w.attempted} attempted, "
        f"{stats['decode_steps']} decode steps, compiles {sv.counter.n}, "
        f"engine post-warm-up variants "
        f"{stats['perf']['post_warmup_variants']}")
    log(tally["gc"])

    # the program's state goes before the reference runs: a process's
    # memory peak never falls again
    params, split = sv.params, sv.split
    sv = None
    gc.collect()
    import check

    g, sample = check.logit_gap(
        params, cell, tr, results, seed,
        modes=("highest", "high") if control else ("highest",))
    by_rid = {it.rid: it for it in tr.window}
    short = sum(1 for r in results if r.status == "completed"
                and len(r.tokens) != by_rid[r.rid].max_new_tokens)
    checks = {"short_requests": {"value": short, "limit": 0},
              "logit_gap": {"value": g["served"],
                            "limit": cell.limits["logit_gap"]}}
    if control:
        checks["control_gap"] = {"value": g.get("high", float("inf")),
                                 "limit": cell.limits["logit_gap"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": w.memory_peak}
    if trace:
        device.update(busy_s=w.trace.busy_s, window_s=w.trace_s)
    return Outcome(w, checks, device, split, sample)


def verdict(checks: dict) -> bool:
    """`correct`: every number compared is within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def control_checks(checks: dict) -> dict:
    """The checks as they read with the control in the program's place:
    its gap stands where the program's served gap stood."""
    out = {k: v for k, v in checks.items() if k != "control_gap"}
    out["logit_gap"] = checks["control_gap"]
    return out


def result_line(cell: Cell, out: Outcome, trace: bool) -> dict:
    """The benchmark's result object. Only a TPU run is a measurement."""
    if out.device["platform"] != "tpu":
        raise NotADevice(
            f"a {out.device['platform']} run is not a device measurement")
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = reader(m["name"])(out.window)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    line = {"correct": verdict(out.checks), "attempted": out.window.attempted,
            "failed": out.window.failed, "metrics": metrics,
            "device": out.device}
    if trace:
        line["breakdown"] = out.window.trace.breakdown()
    line["compared"] = out.checks
    return line


def print_checks(out: Outcome, stream=sys.stderr) -> None:
    for name, c in out.checks.items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=stream)
