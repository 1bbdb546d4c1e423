"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<name>.json`` and draws an open-loop arrival schedule.

Every seed gets the same multiset of requests (pairs of prompt and output
length) and of inter-arrival gaps — stratified quantiles of the mix's
distributions — in a different order, so
the seed changes which request comes when and which token ids a prompt
holds, never how much work a run offers.  Run-to-run spread then measures
the system, not the luck of the draw.

A mix file holds::

    {"rate_rps": 2.0,              # Poisson arrivals, requests per second
     "warm_s": 80,                 # traffic before the window (set-up)
     "drain_s": 30,                # after the window: wait this long at most
                                   #   for the window's first tokens
     "classes": [{"share": 0.9,
                  "prompt": {"dist": "lognormal", "median": 384,
                             "sigma": 1.0, "min": 16, "max": 4096},
                  "output": {"dist": "uniform", "min": 32, "max": 256}}]}
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


@dataclass
class Arrival:
    due_s: float             # when the request is due, from the run's start
    prompt: List[int]        # prompt token ids
    max_new_tokens: int


def load_mix(name: str) -> dict:
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def seed_rng(seed: int) -> np.random.Generator:
    if seed < 0:
        raise SystemExit(f"--seed must be a whole number >= 0, got {seed}")
    return np.random.default_rng(seed)


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles, (i + 0.5) / n, of a length distribution,
    rounded and clipped to ``[min, max]``."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
        x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = lo + q * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.round(x), lo, hi).astype(np.int64)


def class_counts(classes: List[dict], n: int) -> List[int]:
    counts = [int(round(c["share"] * n)) for c in classes[:-1]]
    return counts + [n - sum(counts)]


def horizon_s(mix: dict, seconds: float) -> float:
    """Seconds of arrivals to draw: the warm period, the window and the
    drain."""
    return mix["warm_s"] + seconds + mix["drain_s"]


def arrivals(mix: dict, seconds: float, seed: int, vocab_size: int,
             rate_rps: float | None = None) -> List[Arrival]:
    """The run's arrival schedule, sorted by due time."""
    rng = seed_rng(seed)
    rate = mix["rate_rps"] if rate_rps is None else rate_rps
    n = max(1, math.ceil(rate * horizon_s(mix, seconds)))
    # exponential gaps at stratified quantiles: Poisson arrivals of mean rate
    # `rate`, whose total length is the same for every seed
    q = (np.arange(n) + 0.5) / n
    due = np.cumsum(rng.permutation(-np.log1p(-q) / rate))

    classes = mix["classes"]
    label = rng.permutation(np.repeat(np.arange(len(classes)),
                                      class_counts(classes, n)))
    prompt_len = np.zeros(n, np.int64)
    out_len = np.zeros(n, np.int64)
    for k, c in enumerate(classes):
        idx = np.flatnonzero(label == k)
        # (prompt, output) pairs fixed for every seed; the seed orders them
        pair = np.random.default_rng(k).permutation(len(idx))
        order = rng.permutation(len(idx))
        prompt_len[idx] = quantiles(c["prompt"], len(idx))[order]
        out_len[idx] = quantiles(c["output"], len(idx))[pair][order]
    return [
        Arrival(float(t), rng.integers(1, vocab_size, int(p)).tolist(), int(o))
        for t, p, o in zip(due, prompt_len, out_len)
    ]


def longest_context(mix: dict) -> int:
    """Largest prompt + output any request of the mix can have."""
    return max(c["prompt"]["max"] + c["output"]["max"] for c in mix["classes"])
