"""Reduce a JAX profiler trace (`.xplane.pb`) to the device numbers the
per-layer metrics read: how long the device was busy (the union of the
intervals in which an operation ran on it), how long each operation ran
by name, and the longest idle gaps.

Device planes are named "/device:TPU:<n>"; the operations of a plane are
the events of its "XLA Ops" line, each named by its HLO instruction
("%qattn_paged.9 = f32[...] custom-call(...)"). Compiled Pallas kernels
appear under the name given to `pallas_call` (the paged decode kernel is
`qattn_paged`). A loop's event spans the ops of its body, so busy time is
a union, never a sum.
"""
from __future__ import annotations

import dataclasses
import glob
import os

OPS_LINE = "XLA Ops"
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class DeviceTrace:
    chips: int
    busy_s: float  # mean over chips of the union of op intervals
    span_s: float  # first op start to last op end, mean over chips
    op_s: dict  # op (event name without its number) -> seconds, all chips
    gaps: list  # [(start offset s, length s)], longest first, chip 0

    def time_of(self, op: str) -> float:
        """Seconds the device ran op `op` (e.g. "qattn_paged")."""
        return self.op_s.get(op, 0.0)

    def breakdown(self, n: int = 10) -> dict:
        """The ops that took most time (loops that hold other ops left
        out, since their time is their body's) and the longest gaps."""
        ops = sorted(((k, v) for k, v in self.op_s.items()
                      if k not in CONTAINERS), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[f"idle@{t0:.6f}s", g]
                              for t0, g in self.gaps[:n]]}


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def op_base(event_name: str) -> str:
    """The op an event ran, without its instance number: the event
    "%qattn_paged.9 = f32[...] custom-call(...)" ran "qattn_paged"."""
    name = event_name.split(" = ", 1)[0].lstrip("%")
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def union(intervals) -> tuple[float, list]:
    """(covered length, gaps) of [(start, end)] intervals, in their units."""
    covered, gaps, cur_s, cur_e = 0.0, [], None, None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s > cur_e:
            covered += cur_e - cur_s
            gaps.append((cur_e, s - cur_e))
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        covered += cur_e - cur_s
    return covered, gaps


def reduce_planes(planes) -> DeviceTrace:
    """Reduce profiler planes (objects with .name, .lines; lines with
    .name, .events; events with .name, .start_ns, .duration_ns)."""
    busy, spans, op_s, gaps0, chips = [], [], {}, [], 0
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        ops = [ln for ln in plane.lines if ln.name == OPS_LINE]
        if not ops:
            continue
        chips += 1
        iv = []
        for ev in ops[0].events:
            s, d = float(ev.start_ns), float(ev.duration_ns)
            iv.append((s, s + d))
            name = op_base(ev.name)
            op_s[name] = op_s.get(name, 0.0) + d * 1e-9
        if not iv:
            busy.append(0.0)
            spans.append(0.0)
            continue
        covered, gaps = union(iv)
        busy.append(covered * 1e-9)
        t0 = min(s for s, _ in iv)
        spans.append((max(e for _, e in iv) - t0) * 1e-9)
        if chips == 1:
            gaps0 = sorted(((g0 - t0) * 1e-9, g * 1e-9) for g0, g in gaps)
            gaps0.sort(key=lambda tg: -tg[1])
    if chips == 0:
        raise ValueError("the trace holds no TPU device plane with ops")
    return DeviceTrace(chips, sum(busy) / chips, sum(spans) / chips, op_s,
                       gaps0)


def reduce_file(path: str) -> DeviceTrace:
    from jax.profiler import ProfileData

    return reduce_planes(list(ProfileData.from_file(path).planes))
