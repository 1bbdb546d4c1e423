"""One cell of the benchmark: the program's replica loop, or its fleet of
replicas behind the disaggregated router, built from a configuration file
and the cell's layout, driven in real time by an open-loop arrival schedule,
with the benchmark's own spans and counters wrapped around its layers.

A single replica's window drives ``ReplicaServer`` (``submit``, ``step``,
``finish``: the pieces the program's ``serve()`` runs); a fleet's drives
``DisaggregatedRouter`` and its replicas as the program's ``serve_disagg``
does.  Every engine, loop and router knob is at the program's default; only
sizes and the scheduler policy come from the configuration file, and the
replica layout from ``bench/cells/<cell>.json``.
"""
from __future__ import annotations

import json
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import jax

from bench import flops, weights
from bench.generator import Arrival

CELLS_DIR = Path(__file__).resolve().parent / "cells"


def layout(cell: dict) -> Optional[dict]:
    """The cell's replica layout, ``{"prefill": p, "decode": d}`` from
    ``bench/cells/<cell>.json``, one replica a chip; ``None`` (one replica,
    no router) where the cell has no such file."""
    path = CELLS_DIR / f"{cell['name']}.json"
    if not path.is_file():
        return None
    replicas = json.loads(path.read_text())["replicas"]
    if replicas["prefill"] < 1 or replicas["decode"] < 1 or (
            replicas["prefill"] + replicas["decode"] != cell["chips"]):
        raise SystemExit(f"bench: {path} lays out {replicas} on "
                         f"{cell['chips']} chips: one replica a chip, at "
                         f"least one of each role")
    return replicas


@dataclass
class Probe:
    """Host spans and per-round counters the wrappers record, on
    ``time.perf_counter_ns``."""
    # (name, start, end); in a fleet the name is "<replica>/<layer>"
    spans: List[Tuple[str, int, int]] = field(default_factory=list)
    # per dispatched round: (time, tokens scheduled, positions computed,
    # model FLOPs of the scheduled tokens, rows holding a request), and the
    # name of the replica that dispatched it
    rounds: List[Tuple[int, int, int, int, int]] = field(default_factory=list)
    round_replica: List[str] = field(default_factory=list)
    # while any work was outstanding (in a fleet: on any replica or in the
    # router), and in a fleet while each replica had work of its own
    outstanding: List[Tuple[int, int]] = field(default_factory=list)
    busy: Dict[str, List[Tuple[int, int]]] = field(default_factory=dict)
    compiled: List[str] = field(default_factory=list)   # in the window
    counting: bool = False


@dataclass
class System:
    engine: object          # a fleet's: its first replica's
    sched: object
    server: object
    router: object = None   # a fleet's DisaggregatedRouter

    @property
    def servers(self) -> list:
        return [self.server] if self.router is None else self.router.replicas


def _scheduler(cfg: dict):
    from repro.core.apc import APCConfig
    from repro.core.scheduler import ChunkedPrefillScheduler, SchedulerConfig

    eng, sc = cfg["engine"], cfg["scheduler"]
    return ChunkedPrefillScheduler(SchedulerConfig(
        policy=sc["policy"], token_budget=sc["token_budget"],
        max_seqs=eng["n_slots"],
        apc=APCConfig(**sc["apc"]) if sc.get("apc") is not None else None))


def build(cfg: dict, d: dict, mc, seed: int, replicas: Optional[dict] = None,
          devices=None) -> System:
    """Weights from the seed, engine on the default device with its page
    pool, compiled for every chunk bucket, and the scheduler; with a layout
    (``replicas``), the fleet of ``build_fleet``."""
    if replicas is not None:
        return build_fleet(cfg, d, mc, seed, replicas, devices)
    from repro.engine.engine import EngineConfig, JAXEngine, ReplicaServer
    from repro.engine.kv_cache import pool_for_model

    eng = cfg["engine"]
    params = weights.full(d, seed)
    jax.block_until_ready(params)
    pool = pool_for_model(mc, n_blocks=eng["kv_blocks"])
    engine = JAXEngine(mc, EngineConfig(n_slots=eng["n_slots"],
                                        max_context=eng["max_context"]),
                       params=params, kv_pool=pool)
    del params
    engine.warmup()
    sched = _scheduler(cfg)
    server = ReplicaServer(sched, engine, kv_pool=pool)
    return System(engine, sched, server)


def build_fleet(cfg: dict, d: dict, mc, seed: int, replicas: dict,
                devices) -> System:
    """One replica a device, prefill replicas first, built as the program's
    ``build_disagg`` builds them but with the seed's weights: each engine on
    ``devices[i]`` with its own copy of them, its page pool and scheduler
    as the single replica's, warmed up with the swap kernels that carry the
    handoff; behind ``DisaggregatedRouter`` at its defaults (prefetch on,
    every prefill completion handed off, no cost policy)."""
    from repro.disagg.router import DisaggConfig, DisaggregatedRouter
    from repro.engine.engine import EngineConfig, JAXEngine, ReplicaServer
    from repro.engine.kv_cache import pool_for_model

    eng = cfg["engine"]
    n_p, n_d = replicas["prefill"], replicas["decode"]
    params = weights.full(d, seed)
    jax.block_until_ready(params)
    servers = []
    for i in range(n_p + n_d):
        role, k = ("prefill", i) if i < n_p else ("decode", i - n_p)
        pool = pool_for_model(mc, n_blocks=eng["kv_blocks"])
        engine = JAXEngine(mc, EngineConfig(n_slots=eng["n_slots"],
                                            max_context=eng["max_context"]),
                           params=params, kv_pool=pool, device=devices[i])
        servers.append(ReplicaServer(_scheduler(cfg), engine, kv_pool=pool,
                                     name=f"{role}{k}"))
    del params
    router = DisaggregatedRouter(servers[:n_p], servers[n_p:],
                                 DisaggConfig(n_prefill=n_p, n_decode=n_d))
    for rs in servers:
        rs.engine.warmup(include_swap=True)
    first = servers[0]
    return System(first.engine, first.sched, first, router)


def instrument(system: System, d: dict, probe: Probe) -> None:
    """Wrap each replica's layers' entry points with the benchmark's spans
    and counters: ``schedule`` (scheduler), ``dispatch`` (stage + launch of
    the engine step) and ``drain`` (token readback).  In a fleet each span
    is named ``<replica>/<layer>``."""
    for server in system.servers:
        prefix = "" if system.router is None else f"{server.name}/"
        _instrument_replica(server, d, probe, prefix)

    def on_compile(event: str, _secs: float, **kw) -> None:
        if probe.counting and event == "/jax/core/compile/backend_compile_duration":
            probe.compiled.append(str(kw.get("fun_name")))

    jax.monitoring.register_event_duration_secs_listener(on_compile)


def _instrument_replica(server, d: dict, probe: Probe, prefix: str) -> None:
    sched, engine = server.sched, server.engine
    buckets = engine.cfg.chunk_buckets
    n_slots = engine.cfg.n_slots
    clock = time.perf_counter_ns

    def timed(name: str, fn: Callable):
        name = prefix + name

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
        probe.round_replica.append(server.name)

    dispatch = engine.dispatch

    def counted_dispatch(batch):
        count_round(batch)
        return dispatch(batch)

    sched.schedule = timed("schedule", sched.schedule)
    engine.dispatch = timed("dispatch", counted_dispatch)
    engine.drain = timed("drain", engine.drain)


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

    if system.router is not None:
        return drive_fleet(system, reqs_in, mix, seconds, probe, on_open, tick)
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


def drive_fleet(system: System, reqs_in: List[Arrival], mix: dict,
                seconds: float, probe: Probe,
                on_open: Optional[Callable[[float], None]] = None,
                tick: Optional[Callable[[float], None]] = None) -> Drive:
    """``drive`` for a fleet, stepped as the program's ``serve_disagg``
    steps it: ``router.submit`` when a request is due; each live replica's
    ``step(now)`` then ``router.after_step``; then ``router.pump(now)``.
    Work is outstanding while any replica is busy or the router holds
    handoffs; each replica's own busy intervals go to ``probe.busy``.  At
    the end every replica finishes its in-flight round and a last pump lands
    what that drained."""
    from repro.core.request import Request

    router = system.router
    reqs = [Request(prompt_len=len(a.prompt), max_new_tokens=a.max_new_tokens,
                    arrival_time=a.due_s, prompt_tokens=list(a.prompt))
            for a in reqs_in]
    clock_ns = time.perf_counter_ns
    t0 = time.perf_counter()
    for rs in router.replicas:
        rs.start(t0)
    i, n = 0, len(reqs)
    late: List[float] = []
    window: Optional[Tuple[float, float]] = None
    closed = False
    due_in_window: List = []
    # busy since (perf_counter ns), per replica and for the fleet (None)
    since: Dict[Optional[str], Optional[int]] = {None: None}
    busy_in = {None: probe.outstanding}
    for rs in router.replicas:
        since[rs.name] = None
        busy_in[rs.name] = probe.busy.setdefault(rs.name, [])

    def mark(key: Optional[str], busy: bool) -> None:
        if busy and since[key] is None:
            since[key] = clock_ns()
        elif not busy and since[key] is not None:
            busy_in[key].append((since[key], clock_ns()))
            since[key] = None

    while True:
        now = time.perf_counter() - t0
        while i < n and reqs[i].arrival_time <= now:
            router.submit(reqs[i])
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
        replicas = router.live_replicas
        busy = [rs.busy() for rs in replicas]
        mark(None, any(busy) or router.pending_work())
        progress = False
        for rs, b in zip(replicas, busy):
            mark(rs.name, b)
            status = rs.step(now)
            router.after_step(rs, status, now)
            progress |= status in ("round", "drained", "finalized")
        if router.pump(now) == 0 and not progress:
            t = clock_ns()
            wait = reqs[i].arrival_time - now if i < n else 0.001
            time.sleep(min(max(wait, 0.0), 0.001))
            probe.spans.append(("wait", t, clock_ns()))
    for key in since:
        mark(key, False)
    t_end = time.perf_counter() - t0
    for rs in router.live_replicas:
        rs.finish()
    router.pump(time.perf_counter() - t0)
    return Drive(reqs[:i], t0, window, t_end, late)
