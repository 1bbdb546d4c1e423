"""The in-program recorder (``repro.engine.trace``) on the CPU at the tiny
config: off records nothing; on, each round is one span tree with its
round id, each request one ``queued`` span, one counter tuple per round that
matches the benchmark's own count; the spans reach a profiler capture on
the trace clock; and the step's compiled program names its device
scopes."""
import re
import sys
import time
from pathlib import Path

import jax
import pytest

from repro.configs import tiny_config
from repro.core.scheduler import ChunkedPrefillScheduler, SchedulerConfig
from repro.engine import engine as engine_mod
from repro.engine.engine import EngineConfig, JAXEngine, ReplicaServer
from repro.engine.trace import NO_REQ, Recorder, host_bubbles_ms
from repro.engine.workload import WorkloadSpec, attach_prompt_tokens, sharegpt_like

ROOT = Path(__file__).resolve().parents[1]
SCOPES = ("layer_scan", "qkv", "attention", "kv_write", "attn_out", "ffn", "unembed",
          "sample")
HOST_SPANS = ("round", "schedule", "drain.wait", "drain.deliver", "dispatch",
              "stage", "block_tables", "launch", "on_batch_done")


@pytest.fixture(scope="module")
def engine():
    # split every mixed round (P = 1, 2 of 4 slots), so rounds of both
    # kinds are counted
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(engine_mod.SPLIT_ROW_COST, "oracle", 0)
        eng = JAXEngine(tiny_config("qwen1.5-0.5b"), EngineConfig(
            n_slots=4, max_context=128, chunk_buckets=(1, 16, 32)))
    eng.warmup()
    return eng


def _requests(seed=3):
    cfg = tiny_config("qwen1.5-0.5b")
    reqs = sharegpt_like(WorkloadSpec(n_requests=5, inter_arrival_s=0.0,
                                      max_context=90, max_new_tokens=6,
                                      seed=seed))
    attach_prompt_tokens(reqs, cfg.vocab_size, seed=seed)
    return reqs


def _server(engine):
    sched = ChunkedPrefillScheduler(
        SchedulerConfig(policy="fcfs", token_budget=32, max_seqs=4))
    return ReplicaServer(sched, engine)


def _drive(server, reqs):
    server.start(time.perf_counter())
    for r in reqs:
        server.submit(r)
    for _ in range(500):
        if server.step(server._now()) == "idle":
            break
    server.finish()
    assert all(r.generated == r.max_new_tokens for r in reqs)


@pytest.fixture(scope="module")
def recorded(engine):
    """One run with the recorder on, the benchmark's wrappers around it."""
    sys.path.insert(0, str(ROOT))
    from bench import cell

    server = _server(engine)
    server.trace.on = True
    probe = cell.Probe()
    mc = engine.model_cfg
    d = {"d_model": mc.d_model, "n_heads": mc.n_heads,
         "n_kv_heads": mc.n_kv_heads, "head_dim": mc.resolved_head_dim,
         "d_ff": mc.d_ff, "n_layers": mc.n_layers, "vocab_size": mc.vocab_size}
    mp = pytest.MonkeyPatch()
    # the wrappers' compile listener is process-global: leave it out here
    mp.setattr(jax.monitoring, "register_event_duration_secs_listener",
               lambda fn: None)
    try:
        cell.instrument(cell.System(engine, server.sched, server), d, probe)
    finally:
        mp.undo()
    reqs = _requests()
    _drive(server, reqs)
    for name in ("dispatch", "drain"):     # unwrap the shared engine
        del engine.__dict__[name]
    return server.trace, probe, reqs, server


def test_off_records_nothing(engine):
    server = _server(engine)
    assert not server.trace.on and engine.trace is server.trace
    _drive(server, _requests(seed=4))
    assert server.rounds > 0
    assert server.trace.spans == [] and server.trace.counters == []


def test_off_span_is_shared_and_inert():
    rec = Recorder()
    assert rec.span("a") is rec.span("b")
    with rec.span("a"):
        pass
    assert rec.spans == []


def _within(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_round_span_tree(recorded):
    rec, _, _, server = recorded
    rounds = [s for s in rec.spans if s[0] == "round"]
    assert [s[3] for s in rounds] == list(range(server.rounds))
    for r in rounds:
        inside = [s for s in rec.spans if s[0] not in ("round", "queued")
                  and _within(s, r)]
        # every span of the round carries its id, and no span of the
        # round lies outside it
        assert {s[3] for s in inside} == {r[3]}
        assert sorted(s for s in rec.spans
                      if s[3] == r[3] and s[0] not in ("round", "queued")
                      and s[1] < r[2] and s[2] > r[1]) == sorted(inside)
        names = [s[0] for s in inside]
        for one in ("schedule", "dispatch", "stage", "block_tables", "launch",
                    "on_batch_done"):
            assert names.count(one) == 1, (one, names)
        (dispatch,) = [s for s in inside if s[0] == "dispatch"]
        for child in ("stage", "block_tables", "launch"):
            (c,) = [s for s in inside if s[0] == child]
            assert _within(c, dispatch)
        # host spans of one thread nest or are disjoint
        for a in inside:
            for b in inside:
                if a is not b and a[1] < b[2] and b[1] < a[2]:
                    assert _within(a, b) or _within(b, a), (a, b)
    # the pipelined loop drains round N-1 inside round N
    assert sum(s[0] == "drain.wait" for s in rec.spans) >= server.rounds - 1
    assert all(s[4] == NO_REQ for s in rec.spans if s[0] != "queued")


def test_each_request_queued_once_until_its_first_round(recorded):
    rec, _, reqs, server = recorded
    queued = [s for s in rec.spans if s[0] == "queued"]
    assert sorted(s[4] for s in queued) == sorted(r.req_id for r in reqs)
    rounds = {s[3]: s for s in rec.spans if s[0] == "round"}
    for name, start, end, rid, req_id in queued:
        req = next(r for r in reqs if r.req_id == req_id)
        assert start == int((server.t_start + req.arrival_time) * 1e9)
        assert start <= end
        # it ends in the round that scheduled the request's first chunk,
        # right after that round's schedule span
        (sched,) = [s for s in rec.spans
                    if s[0] == "schedule" and s[3] == rid]
        assert sched[2] <= end <= rounds[rid][2]
    first_rounds = {s[3] for s in queued}
    assert first_rounds <= set(rounds)


def test_counters_match_the_benchmark_count(recorded):
    """Tokens and rows are the benchmark's own count; positions are what the
    step computes: one decode row per slot plus ``P x C`` in a split round,
    where the benchmark still counts ``n_slots x C``."""
    rec, probe, _, server = recorded
    n_slots = server.engine.cfg.n_slots
    assert len(rec.counters) == len(probe.rounds) == server.rounds
    assert [c[0] for c in rec.counters] == list(range(server.rounds))
    split = 0
    for (_, _, tok, pos, rows, C, P), (_, p_tok, p_pos, _, p_rows) in zip(
            rec.counters, probe.rounds):
        assert (tok, rows) == (p_tok, p_rows)
        assert p_pos == n_slots * C
        assert (C, P) in server.engine.round_shapes()
        assert pos == (n_slots + P * C if P else n_slots * C)
        split += P > 0
    assert split > 0


def test_host_bubbles_from_spans():
    spans = [("launch", 0, 5, 0, NO_REQ), ("drain.wait", 10, 20, 1, NO_REQ),
             ("launch", 23, 25, 1, NO_REQ), ("drain.wait", 30, 40, 2, NO_REQ),
             ("schedule", 41, 44, 2, NO_REQ), ("launch", 50, 51, 2, NO_REQ)]
    assert host_bubbles_ms(spans) == pytest.approx([3e-6, 10e-6])


def test_spans_reach_the_profiler_on_the_trace_clock(engine, tmp_path):
    from jax.profiler import ProfileData

    server = _server(engine)
    server.trace.on = True
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test.anchor"):
            anchor = time.perf_counter_ns()
        _drive(server, _requests(seed=5))
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    events = {}
    anchor_trace = None
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "test.anchor":
                    anchor_trace = int(ev.start_ns)
                events.setdefault(ev.name, []).append(int(ev.start_ns))
    assert anchor_trace is not None
    shift = anchor_trace - anchor
    spans = [s for s in server.trace.spans if s[0] != "queued"]
    assert {s[0] for s in spans} >= set(HOST_SPANS)
    for name, start, _, _, _ in spans:
        near = min(abs(t - (start + shift)) for t in events[name])
        assert near <= 100_000, (name, near)


@pytest.mark.parametrize("C", [1, 16])
def test_compiled_step_names_every_scope(engine, C):
    names = re.findall(r'op_name="([^"]*)"', engine.step_hlo(C))
    parts = {p for n in names for p in re.split(r"[/;:]", n)}
    assert set(SCOPES) <= parts


def test_compiled_split_step_names_every_scope(engine):
    """A split round (one decode row per slot and one prefill row) names
    every scope too."""
    names = re.findall(r'op_name="([^"]*)"', engine.step_hlo(16, 1))
    parts = {p for n in names for p in re.split(r"[/;:]", n)}
    assert set(SCOPES) <= parts
