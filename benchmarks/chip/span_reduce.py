"""Reduce a JAX profiler trace (`.xplane.pb`) to where the time went by
the program's own names: device seconds per step executable, device
seconds per named scope of a step body, the program's host spans with
their self time, and the host span that covers each idle gap of the
device.

It reads the same file as `trace_reduce.reduce_file`, but through the
XSpace protobuf itself: `jax.profiler.ProfileData` does not expose the
event metadata that carries each XLA op's scope path (`tf_op`) and
program id. The schema below is the public XPlane one
(tsl/profiler/protobuf/xplane.proto), written out here.

- Modules. Chip 0's "XLA Modules" line holds one event per executable
  run, named "jit_<function>(<program id>)". Device seconds add up per
  base name ("jit_decode_burst").
- Scopes. Each op of the "XLA Ops" line carries `tf_op`, the name stack
  it was traced under ("jit(decode_burst)/while/body/.../kv_append/..."),
  and the program id of its module. An op's self time (its duration less
  the ops nested in it, such as a loop's body) goes to
  "<module>/<scope>", the deepest of `SCOPES` on its path, or to
  "<module>/unscoped" (parameter relayout copies, loop control), and to
  "<module>/<scope>/<op>" by the op's name without its number.
- Host spans. The "/host:CPU" plane holds the program's
  `TraceAnnotation`s ("tick", "decode-burst", ...) on the thread that
  opened them, on the same clock as the device ops. A span's self time is
  its length less what its child spans cover.
- Gaps. Each idle gap of chip 0 (between the union of its op intervals)
  is named after the deepest program span that covers more than half of
  it, or "unattributed".
"""
from __future__ import annotations

import dataclasses
import functools

from trace_reduce import op_base, union

#: the named scopes of the step bodies (serving/decode.py, the prefill and
#: burst bodies of serving/scheduler.py, lm_logits, sample_tokens)
SCOPES = ("qkv", "kv_append", "attend", "mlp", "lm_head", "sample")

#: the program's host spans (serving/scheduler.py, serving/spill.py)
PROGRAM_SPANS = frozenset((
    "tick", "intake", "admit", "prefix-load", "prefill-chunk", "readback",
    "decode-burst", "dispatch", "emit", "idle-wait", "spec-round",
    "state-prefill", "spill", "state-spill", "spill-copy", "restore",
    "state-restore", "restore-copy", "degrade", "generate"))

DEVICE0 = "/device:TPU:0"
HOST = "/host:CPU"


@functools.cache
def schema() -> dict:
    """The XPlane message classes by name ("XSpace", "XPlane", ...)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    OPT, REP = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    I64, U64, DBL = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_DOUBLE
    STR, BYT, MSG = F.TYPE_STRING, F.TYPE_BYTES, F.TYPE_MESSAGE
    pkg = "repro.xplane"
    fd = descriptor_pb2.FileDescriptorProto(
        name="repro_xplane.proto", package=pkg, syntax="proto3")

    def message(name, fields, oneofs=(), parent=None):
        m = (parent.nested_type if parent is not None
             else fd.message_type).add(name=name)
        for o in oneofs:
            m.oneof_decl.add(name=o)
        for f in fields:
            fname, num, ftype, label = f[:4]
            kw = dict(name=fname, number=num, type=ftype, label=label)
            if ftype == MSG:
                kw["type_name"] = f".{pkg}.{f[4]}"
            if len(f) > 5:  # a member of the message's one oneof
                kw["oneof_index"] = f[5]
            m.field.add(**kw)
        return m

    message("XSpace", [("planes", 1, MSG, REP, "XPlane"),
                       ("errors", 2, STR, REP),
                       ("warnings", 3, STR, REP),
                       ("hostnames", 4, STR, REP)])
    plane = message("XPlane", [
        ("id", 1, I64, OPT), ("name", 2, STR, OPT),
        ("lines", 3, MSG, REP, "XLine"),
        ("event_metadata", 4, MSG, REP, "XPlane.EventMetadataEntry"),
        ("stat_metadata", 5, MSG, REP, "XPlane.StatMetadataEntry"),
        ("stats", 6, MSG, REP, "XStat")])
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        e = message(entry, [("key", 1, I64, OPT),
                            ("value", 2, MSG, OPT, value)], parent=plane)
        e.options.map_entry = True
    message("XLine", [("id", 1, I64, OPT), ("display_id", 10, I64, OPT),
                      ("name", 2, STR, OPT), ("display_name", 11, STR, OPT),
                      ("timestamp_ns", 3, I64, OPT),
                      ("duration_ps", 9, I64, OPT),
                      ("events", 4, MSG, REP, "XEvent")])
    message("XEvent", [("metadata_id", 1, I64, OPT),
                       ("offset_ps", 2, I64, OPT, None, 0),
                       ("num_occurrences", 5, I64, OPT, None, 0),
                       ("duration_ps", 3, I64, OPT),
                       ("stats", 4, MSG, REP, "XStat")], oneofs=("data",))
    message("XStat", [("metadata_id", 1, I64, OPT),
                      ("double_value", 2, DBL, OPT, None, 0),
                      ("uint64_value", 3, U64, OPT, None, 0),
                      ("int64_value", 4, I64, OPT, None, 0),
                      ("str_value", 5, STR, OPT, None, 0),
                      ("bytes_value", 6, BYT, OPT, None, 0),
                      ("ref_value", 7, U64, OPT, None, 0)],
            oneofs=("value",))
    message("XEventMetadata", [("id", 1, I64, OPT), ("name", 2, STR, OPT),
                               ("display_name", 4, STR, OPT),
                               ("metadata", 3, BYT, OPT),
                               ("stats", 5, MSG, REP, "XStat"),
                               ("child_id", 6, I64, REP)])
    message("XStatMetadata", [("id", 1, I64, OPT), ("name", 2, STR, OPT),
                              ("description", 3, STR, OPT)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return {m.name: message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{pkg}.{m.name}"))
        for m in fd.message_type}


def read_space(path: str):
    space = schema()["XSpace"]()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


@dataclasses.dataclass(frozen=True)
class Event:
    name: str  # the event metadata's name
    start_ps: int  # on the session's clock, shared by every plane
    dur_ps: int
    stats: dict  # the event's and its metadata's stats, by name


def events(plane, line_name: str, keep=None) -> list:
    """The events of `plane`'s line `line_name` in time order; with
    `keep`, only those whose name it accepts."""
    smeta = {k: v.name for k, v in plane.stat_metadata.items()}
    kept = {k for k, md in plane.event_metadata.items()
            if keep is None or keep(md.name)}

    def value(st):
        kind = st.WhichOneof("value")
        v = getattr(st, kind) if kind else None
        return smeta.get(v, v) if kind == "ref_value" else v

    def stats(seq):
        return {smeta.get(s.metadata_id, ""): value(s) for s in seq}

    out = []
    for ln in plane.lines:
        if ln.name != line_name:
            continue
        base = ln.timestamp_ns * 1000
        for ev in ln.events:
            if ev.metadata_id not in kept:
                continue
            md = plane.event_metadata[ev.metadata_id]
            out.append(Event(md.name, base + ev.offset_ps, ev.duration_ps,
                             {**stats(md.stats), **stats(ev.stats)}))
    out.sort(key=lambda e: (e.start_ps, -e.dur_ps))
    return out


def module_base(name: str) -> str:
    """"jit_decode_burst(1234)" -> "jit_decode_burst"."""
    return name.split("(", 1)[0]


def scope_of(tf_op) -> str:
    """The deepest of `SCOPES` on an op's name stack, or "unscoped"."""
    if not tf_op:
        return "unscoped"
    parts = str(tf_op).split(":", 1)[0].split("/")
    for part in reversed(parts):
        if part in SCOPES:
            return part
    return "unscoped"


def self_times(evs: list) -> list:
    """[(event, self ps)]: each event's duration less the events nested
    inside it (on one line, a nested event starts and ends within its
    parent)."""
    out, stack = [], []  # stack of [end, index into out]
    for ev in evs:
        end = ev.start_ps + ev.dur_ps
        while stack and stack[-1][0] <= ev.start_ps:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= ev.dur_ps
        out.append([ev, ev.dur_ps])
        stack.append([end, len(out) - 1])
    return [(e, s) for e, s in out]


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ps: int
    end_ps: int
    depth: int  # 0 for a span no other program span holds
    self_ps: int
    args: dict


def host_spans(planes) -> list:
    """The program's spans on every thread of the host plane, with their
    nesting depth and self time."""
    out = []
    for plane in planes:
        if plane.name != HOST:
            continue
        for line in {ln.name for ln in plane.lines}:
            evs = events(plane, line,
                         lambda n: n.split("#", 1)[0] in PROGRAM_SPANS)
            ends = []  # ends of the spans holding the current one
            for ev, self_ps in self_times(evs):
                end = ev.start_ps + ev.dur_ps
                while ends and ends[-1] <= ev.start_ps:
                    ends.pop()
                out.append(Span(ev.name.split("#", 1)[0], ev.start_ps, end,
                                len(ends), self_ps, ev.stats))
                ends.append(end)
    out.sort(key=lambda s: (s.start_ps, s.depth))
    return out


def attribute(gaps, spans) -> list:
    """The label of each (start, length) gap: the deepest span covering
    more than half of it, else "unattributed"."""
    labels = []
    for g0, g in gaps:
        g1, best = g0 + g, None
        for sp in spans:
            if sp.start_ps >= g1:
                break
            cover = min(sp.end_ps, g1) - max(sp.start_ps, g0)
            if 2 * cover > g and (best is None or sp.depth > best.depth):
                best = sp
        labels.append(best.name if best is not None else "unattributed")
    return labels


@dataclasses.dataclass
class SpanTrace:
    busy_s: float  # chip 0: union of op intervals
    window_s: float  # chip 0: first op start to last op end
    module_s: dict  # module base name -> device seconds
    scope_s: dict  # "<module>/<scope>" -> device seconds (op self time)
    op_s: dict  # "<module>/<scope>/<op>" -> device seconds (op self time)
    spans: dict  # span name, and "<name>/<kind>" for spans with a kind
    #              arg (dispatches) -> {"count", "total_s", "self_s"}
    gaps: list  # [(offset s from the first op, length s, label)]
    idle_by_label: dict  # label -> idle seconds
    host_ms_per_burst: float  # tick outside readback/idle-wait, per burst

    def module_share(self, prefix: str) -> float:
        """Device seconds of modules whose base name starts with `prefix`
        ("jit_decode_burst", "jit_prefill") over busy seconds."""
        t = sum(v for k, v in self.module_s.items() if k.startswith(prefix))
        return t / self.busy_s if self.busy_s else 0.0

    def scope_seconds(self, scope: str) -> float:
        """Device seconds under `scope` in every module."""
        return sum(v for k, v in self.scope_s.items()
                   if k.rsplit("/", 1)[-1] == scope)

    def summary(self, n: int = 12) -> dict:
        top = sorted(self.gaps, key=lambda g: -g[1])[:n]
        return {
            "busy_s": self.busy_s, "window_s": self.window_s,
            "module_s": dict(sorted(self.module_s.items(),
                                    key=lambda kv: -kv[1])),
            "scope_s": dict(sorted(self.scope_s.items(),
                                   key=lambda kv: -kv[1])),
            "op_s": dict(sorted(self.op_s.items(),
                                key=lambda kv: -kv[1])[:n]),
            "spans": self.spans,
            "idle_by_label": dict(sorted(self.idle_by_label.items(),
                                         key=lambda kv: -kv[1])),
            "idle_gaps": [[f"idle@{t0:.6f}s:{lab}", g]
                          for t0, g, lab in top],
            "host_ms_per_burst": self.host_ms_per_burst,
        }


def reduce_space(space) -> SpanTrace:
    planes = list(space.planes)
    dev = next((p for p in planes if p.name == DEVICE0), None)
    if dev is None:
        raise ValueError(f"the trace holds no {DEVICE0} plane")
    module_of, module_s = {}, {}
    for ev in events(dev, "XLA Modules"):
        base = module_base(ev.name)
        module_s[base] = module_s.get(base, 0.0) + ev.dur_ps * 1e-12
        module_of[ev.name.rpartition("(")[2].rstrip(")")] = base
    ops = events(dev, "XLA Ops")
    if not ops:
        raise ValueError("chip 0 ran no op in the trace")
    scope_s, op_s = {}, {}
    for ev, self_ps in self_times(ops):
        module = module_of.get(str(ev.stats.get("program_id", "")),
                               "unknown")
        key = f"{module}/{scope_of(ev.stats.get('tf_op'))}"
        scope_s[key] = scope_s.get(key, 0.0) + self_ps * 1e-12
        key = f"{key}/{op_base(ev.name)}"
        op_s[key] = op_s.get(key, 0.0) + self_ps * 1e-12
    t0 = ops[0].start_ps
    t_end = max(e.start_ps + e.dur_ps for e in ops)
    covered, gaps = union((e.start_ps, e.start_ps + e.dur_ps) for e in ops)
    spans = host_spans(planes)
    labels = attribute(gaps, spans)
    idle_by = {}
    for (_, g), lab in zip(gaps, labels):
        idle_by[lab] = idle_by.get(lab, 0.0) + g * 1e-12
    per = {}
    for sp in spans:
        keys = [sp.name]
        if "kind" in sp.args:
            keys.append(f"{sp.name}/{sp.args['kind']}")
        for key in keys:
            d = per.setdefault(key, {"count": 0, "total_s": 0.0,
                                     "self_s": 0.0})
            d["count"] += 1
            d["total_s"] += (sp.end_ps - sp.start_ps) * 1e-12
            d["self_s"] += sp.self_ps * 1e-12
    waits = sum(per.get(k, {}).get("total_s", 0.0)
                for k in ("readback", "idle-wait"))
    bursts = per.get("decode-burst", {}).get("count", 0)
    host = (per.get("tick", {}).get("total_s", 0.0) - waits) * 1e3 / bursts \
        if bursts else float("nan")
    return SpanTrace(
        busy_s=covered * 1e-12, window_s=(t_end - t0) * 1e-12,
        module_s=module_s, scope_s=scope_s, op_s=op_s, spans=per,
        gaps=[((g0 - t0) * 1e-12, g * 1e-12, lab)
              for (g0, g), lab in zip(gaps, labels)],
        idle_by_label=idle_by, host_ms_per_burst=host)


def reduce_file(path: str) -> SpanTrace:
    return reduce_space(read_space(path))
