"""One cell of the benchmark: the program's replica loop, built from a
configuration file, driven in real time by an open-loop arrival schedule,
with the benchmark's own spans and counters wrapped around its layers.

The window drives ``ReplicaServer`` (``submit``, ``step``, ``finish``: the
pieces the program's ``serve()`` runs) with every engine and loop knob at
the program's default; only sizes and the scheduler policy come from the
configuration file.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Tuple

import jax

from bench import flops, weights
from bench.generator import Arrival


@dataclass
class Probe:
    """Host spans and per-round counters the wrappers record, on
    ``time.perf_counter_ns``."""
    spans: List[Tuple[str, int, int]] = field(default_factory=list)
    # per dispatched round: (time, tokens scheduled, positions computed,
    # model FLOPs of the scheduled tokens, rows holding a request)
    rounds: List[Tuple[int, int, int, int, int]] = field(default_factory=list)
    outstanding: List[Tuple[int, int]] = field(default_factory=list)
    compiled: List[str] = field(default_factory=list)   # in the window
    counting: bool = False


@dataclass
class System:
    engine: object
    sched: object
    server: object


def build(cfg: dict, d: dict, mc, seed: int) -> System:
    """Weights from the seed, engine on the default device with its page
    pool, compiled for every chunk bucket, and the scheduler."""
    from repro.core.apc import APCConfig
    from repro.core.scheduler import ChunkedPrefillScheduler, SchedulerConfig
    from repro.engine.engine import EngineConfig, JAXEngine, ReplicaServer
    from repro.engine.kv_cache import pool_for_model

    eng, sc = cfg["engine"], cfg["scheduler"]
    params = weights.full(d, seed)
    jax.block_until_ready(params)
    pool = pool_for_model(mc, n_blocks=eng["kv_blocks"])
    engine = JAXEngine(mc, EngineConfig(n_slots=eng["n_slots"],
                                        max_context=eng["max_context"]),
                       params=params, kv_pool=pool)
    del params
    engine.warmup()
    sched = ChunkedPrefillScheduler(SchedulerConfig(
        policy=sc["policy"], token_budget=sc["token_budget"],
        max_seqs=eng["n_slots"],
        apc=APCConfig(**sc["apc"]) if sc.get("apc") is not None else None))
    server = ReplicaServer(sched, engine, kv_pool=pool)
    return System(engine, sched, server)


def instrument(system: System, d: dict, probe: Probe) -> None:
    """Wrap the layers' entry points with the benchmark's spans and
    counters: ``schedule`` (scheduler), ``dispatch`` (stage + launch of the
    engine step) and ``drain`` (token readback)."""
    sched, engine = system.sched, system.engine
    buckets = engine.cfg.chunk_buckets
    n_slots = engine.cfg.n_slots
    clock = time.perf_counter_ns

    def timed(name: str, fn: Callable):
        def wrapper(*a, **kw):
            t = clock()
            try:
                return fn(*a, **kw)
            finally:
                probe.spans.append((name, t, clock()))
        return wrapper

    def count_round(batch) -> None:
        chunks = [c for _, c in batch.prefill_chunks]
        widest = max(chunks + [1 if batch.decode_reqs else 0])
        bucket = next((b for b in buckets if widest <= b), buckets[-1])
        n_tok = sum(chunks) + len(batch.decode_reqs)
        f = 0
        for r, c in batch.prefill_chunks:
            f += flops.chunk_flops(d, r.prefill_done, c,
                                   sampled=r.remaining_prefill - c <= 0)
        for r in batch.decode_reqs:
            pos = r.prefill_done + r.generated - r.folded_tokens - 1
            f += flops.chunk_flops(d, pos, 1, sampled=True)
        rows = len(batch.prefill_chunks) + len(batch.decode_reqs)
        probe.rounds.append((clock(), n_tok, n_slots * bucket, f, rows))

    dispatch = engine.dispatch

    def counted_dispatch(batch):
        count_round(batch)
        return dispatch(batch)

    sched.schedule = timed("schedule", sched.schedule)
    engine.dispatch = timed("dispatch", counted_dispatch)
    engine.drain = timed("drain", engine.drain)

    def on_compile(event: str, _secs: float, **kw) -> None:
        if probe.counting and event == "/jax/core/compile/backend_compile_duration":
            probe.compiled.append(str(kw.get("fun_name")))

    jax.monitoring.register_event_duration_secs_listener(on_compile)


@dataclass
class Drive:
    """What one driven run leaves behind, in seconds from its start ``t0``
    (``perf_counter``)."""
    requests: list
    t0: float
    window: Tuple[float, float]
    t_end: float
    late_s: List[float]


def drive(system: System, reqs_in: List[Arrival], mix: dict, seconds: float,
          probe: Probe, on_open: Optional[Callable[[float], None]] = None,
          tick: Optional[Callable[[float], None]] = None) -> Drive:
    """Submit each request when due, open the window after ``warm_s`` of
    traffic, step the replica until the window has closed and every request
    due inside it has its first token (or ``drain_s`` has passed), then
    finish the in-flight round."""
    from repro.core.request import Request

    server = system.server
    reqs = [Request(prompt_len=len(a.prompt), max_new_tokens=a.max_new_tokens,
                    arrival_time=a.due_s, prompt_tokens=list(a.prompt))
            for a in reqs_in]
    clock_ns = time.perf_counter_ns
    t0 = time.perf_counter()
    server.start(t0)
    i, n = 0, len(reqs)
    late: List[float] = []
    window: Optional[Tuple[float, float]] = None
    closed = False
    busy_since: Optional[int] = None
    due_in_window: List = []
    while True:
        now = time.perf_counter() - t0
        while i < n and reqs[i].arrival_time <= now:
            server.submit(reqs[i])
            late.append(now - reqs[i].arrival_time)
            i += 1
        if window is None:
            if now >= mix["warm_s"]:
                window = (now, now + seconds)
                probe.counting = True
                if on_open is not None:
                    on_open(now)
                now = time.perf_counter() - t0
        elif not closed and now >= window[1]:
            closed = True
            probe.counting = False
            due_in_window = [r for r in reqs
                             if window[0] <= r.arrival_time < window[1]]
        if tick is not None and window is not None:
            tick(now)
        if closed and (now >= window[1] + mix["drain_s"] or all(
                r.first_token_time is not None for r in due_in_window)):
            break
        busy = server.busy()
        if busy and busy_since is None:
            busy_since = clock_ns()
        elif not busy and busy_since is not None:
            probe.outstanding.append((busy_since, clock_ns()))
            busy_since = None
        status = server.step(now)
        if status in ("idle", "starved"):
            t = clock_ns()
            wait = reqs[i].arrival_time - now if i < n else 0.001
            time.sleep(min(max(wait, 0.0), 0.001))
            probe.spans.append(("wait", t, clock_ns()))
    if busy_since is not None:
        probe.outstanding.append((busy_since, clock_ns()))
    t_end = time.perf_counter() - t0
    server.finish()
    return Drive(reqs[:i], t0, window, t_end, late)
