"""In-program recorder of one replica's host spans and per-round counters.

Spans are ``(name, start_ns, end_ns, round_id, req_id)`` on
``time.perf_counter_ns``; ``req_id`` is ``NO_REQ`` for spans that belong to
no single request.  Counters are one tuple per dispatched round,
``(round_id, t_ns, tokens, positions, rows, C, P)``: tokens scheduled
(prefill and decode), positions the step computes (``n_slots + P x C`` in a
split round, ``n_slots x C`` otherwise), rows holding a request, the chunk
bucket ``C`` and the split round's prefill rows ``P`` (0: not split).
Everything stays in memory in plain lists.

Recording is off by default.  Off, a site costs the test of ``on``: nothing
is appended and no annotation is made.  On, each host span also enters
``jax.profiler.TraceAnnotation(name)``, so a profiler capture shows the
program's spans on the host thread beside the device ops.

``ReplicaServer`` owns one recorder and hands it to its engine; the server
sets ``round_id`` to the index of the round it is building.
"""
from __future__ import annotations

import contextlib
import time
from typing import List, Tuple

import jax

NO_REQ = -1

Span = Tuple[str, int, int, int, int]
Counter = Tuple[int, int, int, int, int, int, int]

_OFF = contextlib.nullcontext()       # the span of a recorder that is off


class _On:
    __slots__ = ("rec", "name", "req_id", "ann", "t0")

    def __init__(self, rec: "Recorder", name: str, req_id: int):
        self.rec, self.name, self.req_id = rec, name, req_id

    def __enter__(self):
        self.ann = jax.profiler.TraceAnnotation(self.name)
        self.ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self.ann.__exit__(*exc)
        self.rec.add(self.name, self.t0, t1, self.req_id)
        return False


class Recorder:
    """Host spans and per-round counters of one replica."""

    def __init__(self, on: bool = False):
        self.on = on
        self.round_id = 0
        self.spans: List[Span] = []
        self.counters: List[Counter] = []

    def span(self, name: str, req_id: int = NO_REQ):
        """``with rec.span(name):`` records the block as one span."""
        return _On(self, name, req_id) if self.on else _OFF

    def add(self, name: str, start_ns: int, end_ns: int,
            req_id: int = NO_REQ) -> None:
        """Record a span whose ends were taken elsewhere (no annotation)."""
        self.spans.append((name, start_ns, end_ns, self.round_id, req_id))

    def count(self, tokens: int, positions: int, rows: int, C: int,
              P: int) -> None:
        self.counters.append((self.round_id, time.perf_counter_ns(), tokens,
                              positions, rows, C, P))


def host_bubbles_ms(spans: List[Span]) -> List[float]:
    """Per launched round, the host gap from the end of the latest
    ``drain.wait`` before it (the tokens of the previous round became
    host-visible) to the start of its ``launch``: the time the device had
    nothing of this replica's queued.  Rounds before the first drain have
    none."""
    out: List[float] = []
    ready = None
    for name, s, e, _r, _q in sorted(spans, key=lambda sp: sp[1]):
        if name == "drain.wait":
            ready = e
        elif name == "launch" and ready is not None:
            out.append((s - ready) / 1e6)
    return out
