"""Device time by the program's scopes, and idle device time by the program's
own host spans, from a JAX profiler trace (``.xplane.pb``).

The program names its device ops with ``jax.named_scope`` (``SCOPES``) and
records its host spans with ``repro.engine.trace.Recorder`` on
``time.perf_counter_ns``, the harness's clock, so ``bench.xplane``'s anchor
puts them on the trace clock unchanged.

Each device op's name stack, which ``jax.named_scope`` prefixes, is the
``tf_op`` stat of its event metadata.  ``jax.profiler.ProfileData`` does
not expose event metadata stats and TensorFlow's ``xplane_pb2`` need not be
installed, so ``op_metadata`` reads the few XSpace fields it needs with a
small protobuf wire-format reader.
"""
from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterator, List, Sequence, Tuple

from bench.xplane import DeviceTrace, Interval, gaps, leaves, union

# the program's device scopes; an op under none is ``other``
SCOPES = ("layer_scan", "qkv", "attention", "kv_write", "attn_out", "ffn",
          "unembed", "sample")
OTHER = "other"
NO_SPAN = "none"

# field numbers of tsl/profiler/protobuf/xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_METADATA, _PLANE_STAT_METADATA = 2, 4, 5
_MAP_KEY, _MAP_VALUE = 1, 2
_EVENT_META_NAME, _EVENT_META_STATS = 2, 5
_STAT_META_NAME = 2
_STAT_METADATA_ID, _STAT_UINT, _STAT_INT, _STAT_STR, _STAT_REF = 1, 3, 4, 5, 7

OpMeta = Tuple[str, int]     # (tf_op, program_id)


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for varints, the bytes
    for length-delimited and fixed-width fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"xplane: wire type {wire} of field {field}")
        yield field, value


def _text(b) -> str:
    return bytes(b).decode("utf-8", "replace")


def _plane_ops(plane) -> Dict[str, OpMeta]:
    name_of_stat: Dict[int, str] = {}
    metas = []
    for f, v in _fields(plane):
        if f == _PLANE_STAT_METADATA:
            entry = dict(_fields(v))
            if _MAP_VALUE in entry:
                meta = dict(_fields(entry[_MAP_VALUE]))
                name_of_stat[entry.get(_MAP_KEY, 0)] = _text(
                    meta.get(_STAT_META_NAME, b""))
        elif f == _PLANE_EVENT_METADATA:
            entry = dict(_fields(v))
            if _MAP_VALUE in entry:
                metas.append(entry[_MAP_VALUE])
    out: Dict[str, OpMeta] = {}
    for m in metas:
        name, tf_op, program = "", None, 0
        for f, v in _fields(m):
            if f == _EVENT_META_NAME:
                name = _text(v)
            elif f == _EVENT_META_STATS:
                stat = dict(_fields(v))
                kind = name_of_stat.get(stat.get(_STAT_METADATA_ID))
                if kind == "program_id":
                    program = stat.get(_STAT_UINT, stat.get(_STAT_INT, 0))
                elif kind == "tf_op" and _STAT_STR in stat:
                    tf_op = _text(stat[_STAT_STR])
                elif kind == "tf_op" and _STAT_REF in stat:
                    tf_op = name_of_stat.get(stat[_STAT_REF], "")
        if tf_op is not None:
            out.setdefault(name, (tf_op, program))
    return out


def op_metadata(path: Path) -> Dict[str, OpMeta]:
    """Each TPU op's event metadata name (the name ``bench.xplane.load``
    gives the op) -> its ``tf_op`` stat, the op's name stack (such as
    ``jit(step)/layer_scan/while/body/closed_call/attention/gather:``), and
    the id of the compiled program that holds it."""
    buf = memoryview(Path(path).read_bytes())
    out: Dict[str, OpMeta] = {}
    for f, plane in _fields(buf):
        if f != _SPACE_PLANES:
            continue
        name = next((_text(v) for g, v in _fields(plane) if g == _PLANE_NAME), "")
        if name.startswith("/device:TPU:"):
            out.update(_plane_ops(plane))
    return out


def scope(tf_op: str) -> str:
    """The innermost program scope in an op's name stack, or ``other``."""
    found = [p for p in re.split(r"[/;:]", tf_op or "") if p in SCOPES]
    return found[-1] if found else OTHER


def op_scopes(meta: Dict[str, OpMeta]) -> Dict[str, str]:
    """Each op's program scope.  XLA gives an op it makes itself (a convert
    split out of a fusion, an async slice) the metadata of the loop that
    holds it: such an op, and any op under no scope, takes the scope of its
    first operand in the same program that has one of its own, and keeps its
    own otherwise."""
    own = {name: scope(tf) for name, (tf, _) in meta.items()}
    made = {name for name, (tf, _) in meta.items()
            if own[name] == OTHER or tf.split(":")[0].split("/")[-1] == "while"}
    by_short = {(program, name.split(" = ", 1)[0].lstrip("%")): name
                for name, (_, program) in meta.items()}
    out = dict(own)
    for name in made:
        program = meta[name][1]
        for op in re.findall(r"%([\w.\-]+)", name.partition(" = ")[2]):
            src = by_short.get((program, op))
            if src is not None and src not in made:
                out[name] = own[src]
                break
    return out


def _by_value(d: Dict[str, float], n: int) -> Dict[str, float]:
    return dict(sorted(((k, v / n) for k, v in d.items()), key=lambda kv: -kv[1]))


def device_s_by_scope(trace: DeviceTrace, window: Interval,
                      meta: Dict[str, OpMeta], anchor_host_ns: int) -> Dict[str, float]:
    """Device seconds of the leaf ops in the traced ``window`` (host clock,
    ns) by program scope (``op_scopes``), averaged over the devices, largest
    first; ops under no scope, or with no ``tf_op``, are ``other``.  The
    values sum to the leaf-op total."""
    shift = trace.anchor_ns - anchor_host_ns
    a, b = window[0] + shift, window[1] + shift
    scopes = op_scopes(meta)
    out: Dict[str, float] = defaultdict(float)
    for evs in trace.ops.values():
        for name, s, e in leaves(evs):
            if e > a and s < b:
                out[scopes.get(name, OTHER)] += (min(e, b) - max(s, a)) / 1e9
    return _by_value(out, len(trace.ops))


def idle_by_span(trace: DeviceTrace, window: Interval,
                 outstanding: Sequence[Interval],
                 spans: Sequence[Tuple[str, int, int]],
                 anchor_host_ns: int) -> Dict[str, float]:
    """Idle device seconds while work was outstanding in the traced
    ``window`` (host clock, ns), each stretch named by the innermost program
    span covering it (``none`` where no span does), averaged over the
    devices, largest first.  ``spans`` are the program's nested host spans
    ``(name, start, end)``."""
    shift = trace.anchor_ns - anchor_host_ns
    a, b = window[0] + shift, window[1] + shift
    work = union([(x + shift, y + shift) for x, y in outstanding])
    spans = sorted((s + shift, e + shift, n) for n, s, e in spans
                   if e + shift > a and s + shift < b)
    out: Dict[str, float] = defaultdict(float)
    for evs in trace.ops.values():
        busy = union([(s, e) for _, s, e in evs])
        for x, y in work:
            x, y = max(x, a), min(y, b)
            if x >= y:
                continue
            for g0, g1 in gaps(busy, x, y):
                for name, ns in _innermost(spans, g0, g1):
                    out[name] += ns / 1e9
    return _by_value(out, len(trace.ops))


def _innermost(spans, g0: int, g1: int) -> List[Tuple[str, int]]:
    """``[g0, g1)`` cut at the span boundaries inside it, each piece named by
    the innermost span covering it: of nested spans, the last to start."""
    over = [sp for sp in spans if sp[0] < g1 and sp[1] > g0]
    cuts = sorted({g0, g1} | {t for s, e, _ in over for t in (s, e) if g0 < t < g1})
    out = []
    for p, q in zip(cuts, cuts[1:]):
        cover = [sp for sp in over if sp[0] <= p and sp[1] >= q]
        inner = max(cover, key=lambda sp: (sp[0], -sp[1]), default=None)
        out.append((inner[2] if inner else NO_SPAN, q - p))
    return out
