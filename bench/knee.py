#!/usr/bin/env python3
"""Knee sweep of one cell: its configuration and mix at several arrival
rates and seeds, one process, a fresh replica (or fleet) for each run, each
warmed by the mix's own ``warm_s`` of traffic as the cell is.

    python3 bench/knee.py --workload <cell> --rates 0.3,0.4 --seeds 1,2,3 --seconds 60

For each rate and seed it prints one JSON line: the backlog (requests due
and still without a first token, or, handed off to a decode replica, still
without their second) at the window's start and end and averaged over its
two halves, requests finished per second, TTFT p50/p90, ITL p95 and output
tokens/s.  Then one line per rate with the backlog's growth (second half's
mean less the first's) averaged over the seeds, and a last line with the
knee: the highest rate whose mean growth is at most one request, below
which every swept rate also holds.  Rates run in ascending order, and the
sweep ends at the first rate that fails, since no higher one can be the
knee.  The cells store their rates, as absolute requests/s, in their
traffic files.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent.parent),
                str(Path(__file__).resolve().parent.parent / "src")]

from bench import cell as cellmod, generator, modelcfg, stats  # noqa: E402
from bench.run import load_cell, place_compile_cache, require_chips  # noqa: E402


def waiting(r, t: float) -> bool:
    """Due by ``t`` and still waiting for its first token, or, handed off
    by a fleet's prefill replica, for the decode replica's first."""
    if r.arrival_time > t:
        return False
    if r.first_token_time is None or r.first_token_time > t:
        return True
    return bool(r.handoffs) and r.max_new_tokens > 1 and (
        len(r.token_times) < 2 or r.token_times[1] > t)


def backlog(reqs, t: float) -> int:
    return sum(waiting(r, t) for r in reqs)


def sweep_one(cfg, d, mc, mix, rate: float, seconds: float, seed: int,
              replicas=None, devices=None) -> dict:
    system = cellmod.build(cfg, d, mc, seed, replicas, devices)
    probe = cellmod.Probe()
    cellmod.instrument(system, d, probe)
    mix = dict(mix, drain_s=0)
    arr = generator.arrivals(mix, seconds, seed, d["vocab_size"], rate_rps=rate)
    run = cellmod.drive(system, arr, mix, seconds, probe)
    w0, w1 = run.window
    reqs = run.requests
    e2e = stats.end_to_end([r.arrival_time for r in reqs],
                           [r.first_token_time for r in reqs],
                           [r.token_times for r in reqs], run.window, run.t_end)
    mid = (w0 + w1) / 2
    halves = [sum(backlog(reqs, a + (b - a) * k / 20) for k in range(20)) / 20
              for a, b in ((w0, mid), (mid, w1))]
    del system
    gc.collect()
    return {"rate_rps": rate, "seed": seed, "backlog_open": backlog(reqs, w0),
            "backlog_close": backlog(reqs, w1),
            "backlog_halves": halves,
            "finished_per_s": sum(w0 <= (r.finish_time or -1) < w1
                                  for r in reqs) / (w1 - w0), **e2e}


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=60.0)
    p.add_argument("--seeds", default="1,2,3")
    args = p.parse_args()
    place_compile_cache()
    _, cellspec = load_cell(args.workload)
    devices = require_chips(cellspec["chips"])
    replicas = cellmod.layout(cellspec)
    cfg = modelcfg.load(cellspec["config"])
    mix = generator.load_mix(cellspec["traffic"])
    d, mc = modelcfg.dims(cfg), modelcfg.program_config(cfg)
    growth = {}
    for rate in sorted(float(x) for x in args.rates.split(",")):
        runs = [sweep_one(cfg, d, mc, mix, rate, args.seconds, int(s),
                          replicas, devices)
                for s in args.seeds.split(",")]
        for r in runs:
            print(json.dumps(r), flush=True)
        growth[rate] = sum(b - a for a, b in (r["backlog_halves"] for r in runs)) / len(runs)
        print(json.dumps({"rate_rps": rate, "mean_backlog_growth": growth[rate],
                          "finished_per_s": [r["finished_per_s"] for r in runs]}),
              flush=True)
        if growth[rate] > 1:
            break       # no higher rate can be the knee
    knee = None
    for rate in sorted(growth):
        if growth[rate] > 1:
            break
        knee = rate
    print(json.dumps({"knee_rps": knee}), flush=True)


if __name__ == "__main__":
    main()
