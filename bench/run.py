#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration (``bench/configs``) and a
traffic mix (``bench/traffic``); a cell on several chips lays out one replica
a chip behind the program's router (``bench/cells/<cell>.json``).  The run
makes weights and requests from the seed, builds and warms the program's
replica loop or fleet (set-up), offers the
mix's open-loop load for a warm period and then a window of ``--seconds``,
and checks the served tokens against the float32 reference.  ``--trace 0``
reports the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics
from the benchmark's spans, counters and a device trace of the window's
first seconds.  The last line of stdout is one JSON object; the compared
numbers and their limits are also the last lines of stderr.

There is no CPU fallback: without a TPU, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CACHE_DIR = ROOT / ".jax_cache"        # fixed: the path is part of the key
TRACE_DIR = ROOT / ".bench_trace"
TRACE_S = 5.0                          # traced seconds at the window's start
TRACE_ATTEMPTS = 3


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def load_cell(name: str):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"cells: {sorted(cells)}")
    return spec, cells[name]


def metrics_for(spec: dict, cell: str, trace: int) -> list:
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def place_compile_cache() -> None:
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # every program of the cell stays: a fleet compiles its step programs
    # once per device (the TPU cache key holds the device assignment), more
    # than a size cap set for one replica may hold
    jax.config.update("jax_compilation_cache_max_size", -1)


def require_chips(n: int):
    """The devices, or exit non-zero: no TPU, or fewer chips than asked."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU; JAX's first device is a "
                         f"{devices[0].platform!r} device. No result.")
    if len(devices) < n:
        raise SystemExit(f"bench: the cell needs {n} chips, JAX found "
                         f"{len(devices)}. No result.")
    return devices


def peak_table(kind: str) -> dict:
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in peaks:
        raise SystemExit(f"bench: device kind {kind!r} is not in "
                         f"bench/peaks.json. No result.")
    return peaks[kind]


def reader(name: str):
    """``bench/metrics/<name>.py``, or the file of the name before its first
    dot (``sched_ms.tail`` and ``sched_ms.sat`` share ``sched_ms.py``)."""
    for stem in (name, name.split(".")[0]):
        path = BENCH / "metrics" / f"{stem}.py"
        if path.is_file():
            spec = importlib.util.spec_from_file_location(f"metric_{stem}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise SystemExit(f"bench: no reader for metric {name!r} in bench/metrics")


def engine_info(engine) -> dict:
    c = engine.cfg
    return {"n_slots": c.n_slots, "max_context": c.max_context,
            "kv_blocks": engine.kv_pool.cfg.n_blocks, "pipelined": c.pipelined,
            "use_pallas": c.use_pallas, "kv_layout": c.kv_layout}


def fleet_info(system, reqs, probe, window_ns, peaks: dict) -> dict:
    """Per replica its device, engine settings, memory peak and rounds (in
    all, and dispatched in the window), and the router's handoff counts."""
    w0, w1 = window_ns
    st = system.router.store.stats
    return {
        "replicas": [{"name": s.name, "device": s.engine.device.id,
                      "engine": engine_info(s.engine),
                      "memory_peak_bytes": peaks[str(s.engine.device.id)],
                      "rounds": s.rounds,
                      "rounds_in_window": sum(
                          name == s.name and w0 <= r[0] < w1
                          for r, name in zip(probe.rounds, probe.round_replica))}
                     for s in system.servers],
        "handoffs": {"delivered": st.delivered, "dropped": st.dropped,
                     "colocated": st.colocated, "prefetched": st.prefetched,
                     "bytes_moved": st.bytes_moved},
        "requests_handed_off": sum(r.handoffs > 0 for r in reqs),
        "requests_with_first_token": sum(r.first_token_time is not None
                                         for r in reqs),
    }


def run(args, *, control: bool = False, cell=None, devices=None,
        peak=None) -> dict:
    """One run of the cell; returns the result object (and, with
    ``control``, the control's gap and its own ``correct`` under
    ``"control"``: the same comparison, with the control in the program's
    place).  Tests pass
    ``cell`` (the spec and the cell entry), ``devices`` and ``peak`` in
    place of ``BENCHMARK.json`` and the look for a chip."""
    import jax

    from bench import cell as cellmod, check, generator, modelcfg, stats, xplane

    spec, cellspec = cell or load_cell(args.workload)
    replicas = cellmod.layout(cellspec)
    if devices is None:
        devices = require_chips(cellspec["chips"])
    dev = devices[0]
    if peak is None:
        peak = peak_table(dev.device_kind)
    cfg = modelcfg.load(cellspec["config"])
    mix = generator.load_mix(cellspec["traffic"])
    d = modelcfg.dims(cfg)
    mc = modelcfg.program_config(cfg)
    if generator.longest_context(mix) > cfg["engine"]["max_context"]:
        raise SystemExit("bench: the mix's longest request does not fit the "
                         "configuration's max_context")
    wanted = metrics_for(spec, args.workload, args.trace)

    system = cellmod.build(cfg, d, mc, args.seed, replicas, devices)
    probe = cellmod.Probe()
    cellmod.instrument(system, d, probe)
    arrivals = generator.arrivals(mix, args.seconds, args.seed, d["vocab_size"])
    prompts = {}

    tracing = SimpleNamespace(on=False, trace=None, t0_ns=0, t1_ns=0,
                              anchor=0, attempts=0)

    def start_trace():
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # host spans come from the probe
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        with jax.profiler.TraceAnnotation(xplane.ANCHOR):
            tracing.anchor = time.perf_counter_ns()
        tracing.on, tracing.t0_ns = True, tracing.anchor
        tracing.attempts += 1

    def stop_trace():
        tracing.t1_ns = time.perf_counter_ns()
        jax.block_until_ready([s.engine.last_token for s in system.servers])
        jax.profiler.stop_trace()
        tracing.on = False
        try:
            tracing.trace = xplane.load(xplane.find_xplane(TRACE_DIR))
        except (FileNotFoundError, ValueError) as e:
            # a trace now and then comes back without the device's ops:
            # trace the next seconds of the window instead
            print(f"bench: trace attempt {tracing.attempts}: {e}", file=sys.stderr)
            if tracing.attempts >= TRACE_ATTEMPTS:
                raise

    def on_open(_now):
        if args.trace:
            start_trace()

    def tick(_now):
        if tracing.on and time.perf_counter_ns() - tracing.t0_ns >= TRACE_S * 1e9:
            stop_trace()
            if tracing.trace is None:
                start_trace()

    run_ = cellmod.drive(system, arrivals, mix, args.seconds, probe,
                         on_open=on_open, tick=tick)
    while tracing.on:                   # the window ended inside a trace
        stop_trace()
        if tracing.trace is None:
            start_trace()
    setup_s = run_.t0 + run_.window[0] - T_START
    for a, r in zip(arrivals, run_.requests):
        prompts[r.req_id] = a.prompt

    # the fullest of the cell's devices
    peaks = {str(s.engine.device.id): int(
        (s.engine.device.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for s in system.servers}
    memory_peak = max(peaks.values())

    reqs = run_.requests
    failed = sum(r.shed_reason is not None for r in reqs)
    finished, decoding = [], []
    for r in reqs:
        if r.shed_reason is not None:
            continue
        done = r.finish_time is not None and len(r.output_tokens) == r.max_new_tokens
        (finished if done else decoding).append(
            check.Served(prompts[r.req_id], list(r.output_tokens)))
    e2e = stats.end_to_end(
        [r.arrival_time for r in reqs], [r.first_token_time for r in reqs],
        [r.token_times for r in reqs], run_.window, run_.t_end)
    w0_ns = int((run_.t0 + run_.window[0]) * 1e9)
    w1_ns = int((run_.t0 + run_.window[1]) * 1e9)
    rows = [r[4] for r in probe.rounds if w0_ns <= r[0] < w1_ns]
    info = {
        "requests": len(reqs),
        "due_in_window": sum(run_.window[0] <= r.arrival_time < run_.window[1]
                             for r in reqs),
        "finished": len(finished),
        "rounds_in_window": len(rows),
        # rows holding a request: in the window's first ten rounds and over
        # the window, to show the warm period brought the load to its level
        "rows_busy": {"first_10_rounds": sum(rows[:10]) / max(len(rows[:10]), 1),
                      "window_mean": sum(rows) / max(len(rows), 1),
                      "n_slots": system.engine.cfg.n_slots},
        "compiled_in_window": probe.compiled,
        "generator_late_ms": {
            "p50": 1e3 * sorted(run_.late_s)[len(run_.late_s) // 2],
            "p99": 1e3 * sorted(run_.late_s)[int(0.99 * (len(run_.late_s) - 1))],
            "max": 1e3 * max(run_.late_s)},
        "window_s": list(run_.window),
        "setup_s": setup_s,
    }
    info["engine"] = engine_info(system.engine)
    fleet = system.router is not None
    if fleet:
        info["fleet"] = fleet_info(system, reqs, probe, (w0_ns, w1_ns), peaks)
    # the trace's plane of each replica's device
    planes = {s.name: f"/device:TPU:{s.engine.device.id}" for s in system.servers}
    # each request's first two token stamps, for the handoff's gap
    stamps = [SimpleNamespace(handoffs=r.handoffs, token_ns=[
        int((run_.t0 + t) * 1e9) for t in r.token_times[:2]]) for r in reqs]
    del system, run_.requests
    gc.collect()

    trace = None
    if tracing.trace is not None:
        tr = tracing.trace
        window = (tracing.t0_ns, tracing.t1_ns)
        spans = [s for s in probe.spans
                 if s[2] > window[0] and s[1] < window[1]]
        outstanding = probe.outstanding
        if fleet:
            # each device's own replica's work
            outstanding = {p: probe.busy.get(name, []) for name, p in planes.items()}
        trace = xplane.reduce(tr, window, outstanding, spans, tracing.anchor)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    view = SimpleNamespace(
        probe=probe, window_ns=(w0_ns, w1_ns), trace=trace,
        trace_window_ns=(tracing.t0_ns, tracing.t1_ns), peak=peak, dims=d,
        requests=stamps)
    metrics = {}
    for m in wanted:
        if args.trace:
            value = reader(m["name"])(view)
        else:
            value = setup_s if m["name"] == "setup_s" else e2e.get(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    sample = check.sample(finished, decoding, args.seed)
    limit = cfg["correct"]["max_logit_gap"]
    control_gap = None
    if not sample:
        gap = None
    elif control:
        gap, control_gap = check.control_gaps(d, args.seed, sample)
    else:
        gap = check.served_gap(d, args.seed, sample)
    info["compared"] = {"requests": len(sample),
                        "tokens": sum(len(s.output) for s in sample)}
    checks = {
        "max_logit_gap": {"value": gap, "limit": limit},
        "failed_requests": {"value": failed, "limit": 0},
    }
    correct = gap is not None and gap <= limit and failed == 0
    control_correct = (control_gap is not None and control_gap <= limit
                       and failed == 0)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(reqs), "failed": failed,
              "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": [list(x) for x in trace["device_ops"]],
                               "idle_gaps": [list(x) for x in trace["idle_gaps"]]}
        info["trace"] = {k: trace[k] for k in ("work_s", "idle_work_s")}
        if fleet:
            info["trace"]["per_device"] = trace["per_device"]
    result["checks"] = checks
    if control:
        result["control"] = {"correct": control_correct, "gap": control_gap}
    result["info"] = info
    return result


def report(result: dict) -> None:
    info = result.pop("info")
    result.pop("control", None)
    print(json.dumps({"info": info}), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None) -> None:
    args = parse(argv)
    place_compile_cache()
    report(run(args))


if __name__ == "__main__":
    main()
