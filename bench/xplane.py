"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy time (the union of the intervals in which an
operation ran), each device's idle time while its replica had work
outstanding, the operations that took most time, and the longest idle gaps,
each named by what the host was doing in it.

Host spans are recorded by the harness on ``time.perf_counter_ns``; one
``TraceAnnotation`` (``ANCHOR``) taken right after the trace starts puts
that clock on the trace's clock.
"""
from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Sequence, Tuple, Union

ANCHOR = "bench.anchor"
OPS_LINE = "XLA Ops"
TOP = 10

Interval = Tuple[int, int]


@dataclass
class DeviceTrace:
    ops: Dict[str, List[Tuple[str, int, int]]]   # device -> (op, start, end)
    anchor_ns: int                                # ANCHOR's start, trace clock


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: Path) -> DeviceTrace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    ops: Dict[str, List[Tuple[str, int, int]]] = {}
    anchor = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [
                        (ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == ANCHOR:
                        anchor = int(ev.start_ns)
    if not ops:
        raise ValueError(f"no '{OPS_LINE}' line on any TPU plane of {path}")
    if anchor is None:
        raise ValueError(f"no {ANCHOR} host event in {path}")
    return DeviceTrace(ops, anchor)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def overlap(merged: Sequence[Interval], a: int, b: int) -> int:
    """Length of ``[a, b)`` covered by disjoint sorted intervals."""
    return sum(max(0, min(y, b) - max(x, a)) for x, y in merged)


def gaps(merged: Sequence[Interval], a: int, b: int) -> List[Interval]:
    """The parts of ``[a, b)`` that no interval covers."""
    out, t = [], a
    for x, y in merged:
        if y <= a or x >= b:
            continue
        if x > t:
            out.append((t, x))
        t = max(t, y)
    if t < b:
        out.append((t, b))
    return out


def reduce(trace: DeviceTrace, window: Interval,
           outstanding: Union[Sequence[Interval], Dict[str, Sequence[Interval]]],
           host_spans: Sequence[Tuple[str, int, int]], anchor_host_ns: int) -> dict:
    """Numbers of the traced ``window`` (host clock, ns): busy seconds
    averaged over the devices, idle seconds while work was outstanding, the
    top device operations and the longest idle gaps by host span, and each
    device's own busy, work and idle seconds (``per_device``).

    ``outstanding`` holds the intervals in which work was outstanding, for
    every device alike, or per device plane (a fleet: each replica's own).
    Over several devices an op or a gap is labelled with its device, and the
    lists take each device's largest in turn, so that every device shows."""
    shift = trace.anchor_ns - anchor_host_ns        # host ns -> trace ns
    a, b = window[0] + shift, window[1] + shift
    spans = sorted((s + shift, e + shift, n) for n, s, e in host_spans)
    many = len(trace.ops) > 1

    def shifted(intervals):
        return union([(x + shift, y + shift) for x, y in intervals])

    common = None if isinstance(outstanding, dict) else shifted(outstanding)
    per_device: Dict[str, dict] = {}
    ops_of: List[List[Tuple[str, float]]] = []
    gaps_of: List[List[Tuple[str, float]]] = []
    for plane, evs in trace.ops.items():
        tag = f"{plane.rsplit('device:', 1)[-1]} " if many else ""
        work = common if common is not None else shifted(outstanding.get(plane, []))
        busy = union([(s, e) for _, s, e in evs])
        by_op: Dict[str, float] = defaultdict(float)
        for name, s, e in leaves(evs):
            by_op[tag + label(name)] += max(0, min(e, b) - max(s, a)) / 1e9
        idle: List[Tuple[str, float]] = []
        work_s = idle_work_s = 0.0
        for x, y in work:
            x, y = max(x, a), min(y, b)
            if x >= y:
                continue
            work_s += (y - x) / 1e9
            for g0, g1 in gaps(busy, x, y):
                idle_work_s += (g1 - g0) / 1e9
                idle.append((tag + _label(spans, g0, g1), (g1 - g0) / 1e9))
        per_device[plane] = {"busy_s": overlap(busy, a, b) / 1e9,
                             "work_s": work_s, "idle_work_s": idle_work_s}
        ops_of.append(sorted(by_op.items(), key=lambda kv: -kv[1]))
        gaps_of.append(sorted(idle, key=lambda kv: -kv[1]))
    n = len(trace.ops)
    return {
        "busy_s": sum(v["busy_s"] for v in per_device.values()) / n,
        "window_s": (b - a) / 1e9,
        "work_s": sum(v["work_s"] for v in per_device.values()) / n,
        "idle_work_s": sum(v["idle_work_s"] for v in per_device.values()) / n,
        "device_ops": _take_turns(ops_of),
        "idle_gaps": _take_turns(gaps_of),
        "per_device": per_device,
    }


def _take_turns(lists: Sequence[Sequence[Tuple[str, float]]]) -> list:
    """The first ``TOP`` entries of each device's list (largest first),
    taken one device at a time, largest first in each turn."""
    out: list = []
    for k in range(TOP):
        turn = sorted((lst[k] for lst in lists if k < len(lst)),
                      key=lambda kv: -kv[1])
        out.extend(turn[:TOP - len(out)])
        if len(out) >= TOP:
            break
    return out


def leaves(evs: Sequence[Tuple[str, int, int]]) -> List[Tuple[str, int, int]]:
    """The events that hold no other: a loop or call op on the ops line
    spans the ops of its body, which are counted instead."""
    evs = sorted(evs, key=lambda x: (x[1], -x[2]))
    return [ev for ev, nxt in zip(evs, evs[1:] + [None])
            if nxt is None or nxt[1] >= ev[2]]


def label(hlo: str) -> str:
    """``%fusion.12 = bf16[16,256]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.12 bf16[16,256]``: the op and its result's shape."""
    head, _, rest = hlo.partition(" = ")
    return f"{head.lstrip('%')} {rest.split('{')[0]}".strip()


def _label(spans, g0: int, g1: int) -> str:
    """The host span that covers most of the gap ``[g0, g1)``."""
    best, name = 0, "host"
    for s, e, n in spans:
        if s >= g1:
            break
        o = min(e, g1) - max(s, g0)
        if o > best:
            best, name = o, n
    return name
