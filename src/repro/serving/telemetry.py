"""First-class serving telemetry: metrics registry + structured tracer.

Until this module existed, every number the serving stack produced was a
dict snapshot assembled *after* a benchmark loop finished — useless for
answering "why did this request's TTFT spike" or "what did the pressure
ladder do at t=3.2s" while the trace is still running. This module is the
one source of truth those snapshots become views over:

  MetricsRegistry   zero-dependency counters / gauges / explicit-bucket
                    histograms, optionally labelled, rendered in the
                    Prometheus text exposition format (``GET /metrics`` on
                    `serving/server.py`). Metric updates are plain host
                    arithmetic on python floats — they never touch device
                    memory, rng streams, or jit dispatch, so a metered run
                    is bitwise-identical to an unmetered one by
                    construction (pinned in tests/test_telemetry.py).

  Tracer            spans with two sinks. Under a profiler session,
                    `Tracer.region(name, **args)` opens a
                    `jax.profiler.TraceAnnotation`, so the span lands in
                    the same `.xplane.pb` as the device ops, on the
                    profiler's clock (with no session that costs one
                    check). When `enabled` it also pushes the span into
                    a ring of host events: the scheduler's tick, intake,
                    admit, prefix-load, prefill-chunk, decode-burst,
                    dispatch, readback, emit, idle-wait, spec-round,
                    spill, restore and degrade spans, plus instants
                    (shed, cancel, watchdog, fault), with page/byte
                    deltas and request ids. The ring bound
                    (`SchedulerConfig.trace_capacity`) makes it
                    soak-safe. Export is Chrome/Perfetto ``trace_event``
                    JSON (``GET /trace``, or ``benchmarks/soak.py
                    --trace-out``) — load it at https://ui.perfetto.dev.
                    The ring is the part gated by `Telemetry.enabled`.

  Telemetry         the facade the engine owns: `.registry` + `.tracer`.

Metric/stat equivalence contract: `PagedServingEngine.run` snapshots the
registry at entry and builds its returned ``stats[...]`` blocks from the
per-run *delta* (`MetricsRegistry.snapshot` / `RegistryDelta`), so the
dict a benchmark pins and the exposition a scraper reads cannot drift
apart — asserted equal in tests/test_telemetry.py and gated by the CI
telemetry-smoke job. The registry itself is cumulative over the engine's
lifetime (Prometheus semantics: counters only ever go up; restarts are
what reset them).
"""
from __future__ import annotations

import collections
import json
import re
import threading
import time
from typing import Optional

from jax import profiler as jax_profiler

#: default explicit bucket bounds (seconds) for latency-shaped histograms
#: (TTFT, TPOT, end-to-end). Upper bounds, +Inf implicit.
LATENCY_BUCKETS_S = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
)

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")

#: every exposed metric is prefixed so a shared Prometheus cannot collide
PROM_PREFIX = "repro_"

#: trace_event phases the exporter emits: complete spans, instants, and
#: thread-name metadata. Anything else in a /trace payload is a bug.
TRACE_PHASES = ("X", "i", "M")


def _fmt(v: float) -> str:
    """Prometheus float formatting: integers render bare, floats repr."""
    f = float(v)
    return str(int(f)) if f == int(f) and abs(f) < 1e15 else repr(f)


class Counter:
    """Monotonic metric. `inc` by any non-negative amount (float ok —
    `prefill_wall_s` is a counter of seconds)."""

    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def inc(self, amount: float = 1.0) -> None:
        if amount < 0:
            raise ValueError(f"counter decrement ({amount}) is not a thing")
        self.value += amount


class Gauge:
    """Point-in-time metric (pool occupancy, live slots)."""

    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def set(self, value: float) -> None:
        self.value = float(value)

    def inc(self, amount: float = 1.0) -> None:
        self.value += amount

    def dec(self, amount: float = 1.0) -> None:
        self.value -= amount


class Histogram:
    """Explicit-bucket histogram (Prometheus semantics).

    `bounds` are strictly-increasing upper bounds; an implicit +Inf
    bucket catches the overflow. `counts[i]` is the NON-cumulative count
    of observations with `v <= bounds[i]` (last slot = +Inf overflow);
    the exposition renderer derives the cumulative `le` series.
    """

    __slots__ = ("bounds", "counts", "sum", "count")

    def __init__(self, bounds=LATENCY_BUCKETS_S):
        bounds = tuple(float(b) for b in bounds)
        if not bounds:
            raise ValueError("histogram needs at least one bucket bound")
        if any(b >= c for b, c in zip(bounds, bounds[1:])):
            raise ValueError(f"bucket bounds must strictly increase: "
                             f"{bounds}")
        self.bounds = bounds
        self.counts = [0] * (len(bounds) + 1)
        self.sum = 0.0
        self.count = 0

    def observe(self, value: float) -> None:
        v = float(value)
        lo, hi = 0, len(self.bounds)  # first bucket with v <= bound
        while lo < hi:
            mid = (lo + hi) // 2
            if v <= self.bounds[mid]:
                hi = mid
            else:
                lo = mid + 1
        self.counts[lo] += 1
        self.sum += v
        self.count += 1

    def state(self) -> dict:
        """The view `stats[...]` blocks embed and deltas subtract."""
        return {"buckets": list(self.bounds),
                "counts": list(self.counts),
                "sum": self.sum, "count": self.count}


class MetricsRegistry:
    """Name + label-keyed store of Counter / Gauge / Histogram.

    `counter/gauge/histogram` are get-or-create (idempotent, cheap after
    first call — one dict lookup), so instrumentation sites just ask for
    the metric by name. A name is permanently one type; asking for it as
    another raises. Thread-safe for the create path (the server scrapes
    from another thread); updates on the returned objects are plain
    attribute arithmetic guarded by the GIL.
    """

    def __init__(self):
        self._metrics: dict[tuple, object] = {}  # (name, labels) -> metric
        self._meta: dict[str, tuple] = {}  # name -> (kind, help)
        self._lock = threading.Lock()

    @staticmethod
    def _key(name: str, labels: dict) -> tuple:
        return (name, tuple(sorted(labels.items())))

    def _get(self, kind: str, name: str, help: str, labels: dict,
             factory):
        if not _NAME_RE.match(name):
            raise ValueError(f"invalid metric name {name!r}")
        for k in labels:
            if not _LABEL_RE.match(k):
                raise ValueError(f"invalid label name {k!r}")
        key = self._key(name, labels)
        m = self._metrics.get(key)
        if m is not None:
            if self._meta[name][0] != kind:
                raise ValueError(
                    f"metric {name!r} is a {self._meta[name][0]}, not a "
                    f"{kind}")
            return m
        with self._lock:
            m = self._metrics.get(key)
            if m is None:
                meta = self._meta.setdefault(name, (kind, help))
                if meta[0] != kind:
                    raise ValueError(
                        f"metric {name!r} is a {meta[0]}, not a {kind}")
                m = factory()
                self._metrics[key] = m
        return m

    def counter(self, name: str, help: str = "", **labels) -> Counter:
        return self._get("counter", name, help, labels, Counter)

    def gauge(self, name: str, help: str = "", **labels) -> Gauge:
        return self._get("gauge", name, help, labels, Gauge)

    def histogram(self, name: str, help: str = "",
                  buckets=LATENCY_BUCKETS_S, **labels) -> Histogram:
        return self._get("histogram", name, help, labels,
                         lambda: Histogram(buckets))

    # ------------------------------------------------------------ snapshots --
    def snapshot(self) -> dict:
        """Point-in-time copy of every metric's state, for per-run deltas
        (`RegistryDelta`). Gauges snapshot too, but deltas report their
        CURRENT value — a gauge difference is meaningless."""
        out = {}
        for (name, labels), m in list(self._metrics.items()):
            if isinstance(m, Histogram):
                out[(name, labels)] = m.state()
            else:
                out[(name, labels)] = m.value
        return out

    def delta(self, since: dict) -> "RegistryDelta":
        return RegistryDelta(self, since)

    # ------------------------------------------------------------ exposition --
    def render_prometheus(self) -> str:
        """The text exposition format v0.0.4 (`GET /metrics`)."""
        by_name: dict[str, list] = collections.defaultdict(list)
        for (name, labels), m in sorted(self._metrics.items()):
            by_name[name].append((dict(labels), m))
        lines = []
        for name, entries in by_name.items():
            kind, help = self._meta[name]
            full = PROM_PREFIX + name + ("_total" if kind == "counter"
                                         else "")
            if help:
                lines.append(f"# HELP {full} {help}")
            lines.append(f"# TYPE {full} {kind}")
            for labels, m in entries:
                lab = _render_labels(labels)
                if isinstance(m, Histogram):
                    base = PROM_PREFIX + name
                    cum = 0
                    for bound, c in zip(m.bounds, m.counts):
                        cum += c
                        lines.append(
                            f"{base}_bucket"
                            f"{_render_labels(labels, le=_fmt(bound))} "
                            f"{cum}")
                    lines.append(
                        f"{base}_bucket"
                        f"{_render_labels(labels, le='+Inf')} {m.count}")
                    lines.append(f"{base}_sum{lab} {_fmt(m.sum)}")
                    lines.append(f"{base}_count{lab} {m.count}")
                else:
                    lines.append(f"{full}{lab} {_fmt(m.value)}")
        return "\n".join(lines) + "\n"


def _render_labels(labels: dict, **extra) -> str:
    items = {**labels, **extra}
    if not items:
        return ""
    inner = ",".join(
        f'{k}="{_escape_label(str(v))}"' for k, v in items.items())
    return "{" + inner + "}"


def _escape_label(v: str) -> str:
    return v.replace("\\", r"\\").replace('"', r"\"").replace("\n", r"\n")


class RegistryDelta:
    """Per-run view over a registry: current state minus a snapshot.

    The scheduler builds its returned ``stats[...]`` blocks from exactly
    this object, which is what makes the dicts *views over the registry*
    rather than a second bookkeeping system that can drift.
    """

    def __init__(self, registry: MetricsRegistry, since: dict):
        self._reg = registry
        self._since = since

    def value(self, name: str, **labels) -> float:
        """Counter delta (or gauge CURRENT value) for (name, labels)."""
        key = MetricsRegistry._key(name, labels)
        m = self._reg._metrics.get(key)
        if m is None:
            return 0.0
        if isinstance(m, Histogram):
            raise ValueError(f"{name!r} is a histogram; use .hist()")
        if self._reg._meta[name][0] == "gauge":
            return m.value
        base = self._since.get(key, 0.0)
        return m.value - base

    def hist(self, name: str, **labels) -> dict:
        """Histogram delta state ({"buckets","counts","sum","count"})."""
        key = MetricsRegistry._key(name, labels)
        m = self._reg._metrics.get(key)
        if m is None:
            return {"buckets": [], "counts": [], "sum": 0.0, "count": 0}
        if not isinstance(m, Histogram):
            raise ValueError(f"{name!r} is not a histogram")
        cur = m.state()
        base = self._since.get(key)
        if base is None:
            return cur
        return {
            "buckets": cur["buckets"],
            "counts": [a - b for a, b in zip(cur["counts"],
                                             base["counts"])],
            "sum": cur["sum"] - base["sum"],
            "count": cur["count"] - base["count"],
        }


# ---------------------------------------------------------------- tracer ----
class Region:
    """One open span of `Tracer.region`.

    `set(**args)` adds args known only at exit (such as a burst's emitted
    count); they reach the ring, not the profiler, whose annotation took
    its args at entry. `elapsed` is the span's length in seconds once it
    has closed, measured whether or not the tracer is enabled."""

    __slots__ = ("_tracer", "_name", "_tid", "_args", "_ann", "_t0",
                 "elapsed")

    def __init__(self, tracer: "Tracer", name: str, tid: int, args: dict,
                 ann):
        self._tracer, self._name, self._tid = tracer, name, tid
        self._args, self._ann = args, ann
        self._t0 = self.elapsed = 0.0

    def set(self, **args) -> None:
        self._args.update(args)

    def __enter__(self) -> "Region":
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.elapsed = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        tr = self._tracer
        if tr.enabled:
            tr._push({"name": self._name, "ph": "X",
                      "ts": self._t0 - tr._epoch, "dur": self.elapsed,
                      "tid": self._tid, "args": self._args})


class Tracer:
    """Spans on the profiler's clock, plus a ring of host events.

    `region(...)` is the one span API (see `Region`). Ring events are host
    dicts: ``{"name", "ph", "ts", "dur", "tid", "args"}`` with `ts`/`dur`
    in SECONDS relative to the tracer's epoch (perf counter at
    construction; `reset_epoch` realigns — the engine calls it at `run()`
    entry so trace timestamps read as trace time). The ring (`capacity`)
    bounds memory for soak-length runs: old events fall off the front,
    newest always survive — the property the watchdog's flight-recorder
    dump relies on.

    A disabled tracer (`enabled=False`) pushes nothing into the ring; its
    regions still annotate a running profiler session and still time
    themselves.
    """

    def __init__(self, capacity: int = 4096, enabled: bool = True):
        if capacity < 16:
            raise ValueError(f"trace capacity must be >= 16, got "
                             f"{capacity}")
        self.capacity = capacity
        self.enabled = enabled
        self._ring = collections.deque(maxlen=capacity)
        self._epoch = time.perf_counter()
        self.dropped = 0  # events pushed out of the ring
        self.emitted = 0  # events ever emitted

    # -------------------------------------------------------------- record --
    def reset_epoch(self) -> None:
        if self.enabled:
            self._epoch = time.perf_counter()

    def region(self, name: str, tid: int = 0, *,
               step_num: Optional[int] = None, **args) -> Region:
        """A span around a `with` block. Under a profiler session the
        profile gets `name` with `args` (a `StepTraceAnnotation` when
        `step_num` is given); with none, the annotation is skipped after
        one check. The ring, when enabled, gets the same name and args
        plus any the block `set`s. Pass args as values, never as
        pre-formatted strings: the profiler formats them only under a
        session."""
        ann = None
        if jax_profiler.TraceAnnotation.is_enabled():
            if step_num is None:
                ann = jax_profiler.TraceAnnotation(name, **args)
            else:
                ann = jax_profiler.StepTraceAnnotation(
                    name, step_num=step_num, **args)
        return Region(self, name, tid, args, ann)

    def instant(self, name: str, tid: int = 0, **args) -> None:
        if not self.enabled:
            return
        self._push({"name": name, "ph": "i",
                    "ts": time.perf_counter() - self._epoch, "dur": 0.0,
                    "tid": tid, "args": args})

    def _push(self, ev: dict) -> None:
        if len(self._ring) == self.capacity:
            self.dropped += 1
        self._ring.append(ev)
        self.emitted += 1

    # -------------------------------------------------------------- export --
    def events(self) -> list[dict]:
        return list(self._ring)

    def tail(self, n: int) -> list[dict]:
        """Last `n` events — the watchdog's flight recorder."""
        if n <= 0:
            return []
        ring = list(self._ring)
        return ring[-n:]

    def clear(self) -> None:
        self._ring.clear()

    def to_perfetto(self, pid: int = 1) -> dict:
        """Chrome/Perfetto ``trace_event`` JSON (the object form, so a
        `displayTimeUnit` and metadata ride along). `ts`/`dur` convert to
        microseconds, the format's unit. Load at https://ui.perfetto.dev
        or chrome://tracing."""
        ring = self.events()  # a snapshot: the serve loop keeps pushing
        events = [{
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "ts": 0, "args": {"name": "repro-serving"},
        }]
        tids = sorted({ev["tid"] for ev in ring})
        for tid in tids:
            events.append({
                "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
                "ts": 0,
                "args": {"name": ("scheduler" if tid == 0
                                  else f"slot {tid - 1}")},
            })
        for ev in ring:
            out = {
                "name": ev["name"], "ph": ev["ph"], "pid": pid,
                "tid": ev["tid"], "ts": round(ev["ts"] * 1e6, 3),
                "args": ev["args"],
            }
            if ev["ph"] == "X":
                out["dur"] = round(ev["dur"] * 1e6, 3)
            else:
                out["s"] = "t"  # instant scope: thread
            events.append(out)
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "emitted": self.emitted,
                "dropped": self.dropped,
                "capacity": self.capacity,
            },
        }

    def to_perfetto_json(self, **kw) -> str:
        return json.dumps(self.to_perfetto(**kw))


#: a tracer with its ring off, for callers that were given none: its
#: regions still annotate a profiler session
NULL_TRACER = Tracer(capacity=16, enabled=False)


def validate_trace(doc: dict) -> list[str]:
    """Schema check a ``/trace`` payload (the CI telemetry-smoke gate and
    tests share it). Returns a list of violations — empty means valid."""
    errs = []
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        return ["missing traceEvents"]
    evs = doc["traceEvents"]
    if not isinstance(evs, list):
        return ["traceEvents is not a list"]
    for i, ev in enumerate(evs):
        for field in ("name", "ph", "ts", "pid", "tid"):
            if field not in ev:
                errs.append(f"event {i}: missing {field!r}")
        ph = ev.get("ph")
        if ph not in TRACE_PHASES:
            errs.append(f"event {i}: bad phase {ph!r}")
        if ph == "X" and (not isinstance(ev.get("dur"), (int, float))
                          or ev["dur"] < 0):
            errs.append(f"event {i}: span without non-negative dur")
        if not isinstance(ev.get("ts"), (int, float)):
            errs.append(f"event {i}: non-numeric ts")
        if len(errs) > 20:
            errs.append("...")
            break
    return errs


# ---------------------------------------------------------------- facade ----
class Telemetry:
    """What the serving engine owns: a metrics registry (always live —
    metric math is host-side and free of device/rng effects, and the
    ``stats[...]`` views are built from it) plus a tracer (gated by
    `enabled`; the event ring is the only part with per-event cost worth
    switching off)."""

    def __init__(self, enabled: bool = True, trace_capacity: int = 4096):
        self.enabled = enabled
        self.registry = MetricsRegistry()
        self.tracer = Tracer(capacity=trace_capacity, enabled=enabled)


def format_histogram(hist: dict, title: str, scale: float = 1e3,
                     unit: str = "ms", width: int = 24) -> str:
    """Render a histogram view (`Histogram.state()` / `RegistryDelta
    .hist()`) as a compact one-line-per-occupied-bucket summary — what
    the serve CLI prints instead of raw dicts.

    `scale` converts the bucket bounds for display (1e3: s -> ms)."""
    counts = hist.get("counts") or []
    bounds = hist.get("buckets") or []
    n = hist.get("count", 0)
    if not n:
        return f"  {title}: (no observations)"
    mean = hist["sum"] / n
    peak = max(counts)
    lines = [f"  {title}: n={n} mean={mean * scale:.2f} {unit}"]
    lo = 0.0
    for i, c in enumerate(counts):
        hi = bounds[i] if i < len(bounds) else float("inf")
        if c:
            bar = "#" * max(1, round(width * c / peak))
            hi_s = f"{hi * scale:g}" if hi != float("inf") else "+Inf"
            lines.append(
                f"    {lo * scale:>8g} .. {hi_s:>8} {unit}: "
                f"{c:>5d} {bar}")
        lo = hi
    return "\n".join(lines)


def parse_prometheus(text: str) -> dict:
    """Minimal exposition-format parser for tests and the smoke gate:
    returns {metric_name_with_labels: float}. Raises ValueError on a
    malformed line — which is the point (the CI job 'parses as
    Prometheus exposition')."""
    out = {}
    sample_re = re.compile(
        r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"
        r"(\{[^}]*\})?\s+(-?(?:\d+\.?\d*(?:e-?\d+)?|inf|nan))$",
        re.IGNORECASE)
    for ln in text.splitlines():
        if not ln.strip() or ln.startswith("#"):
            continue
        m = sample_re.match(ln)
        if m is None:
            raise ValueError(f"malformed exposition line: {ln!r}")
        out[m.group(1) + (m.group(2) or "")] = float(m.group(3))
    return out
