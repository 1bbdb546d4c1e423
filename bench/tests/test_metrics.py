"""Each per-layer reader on a hand-made run: what it reads, and that it
reads nothing where its source is missing."""
from types import SimpleNamespace as NS

import pytest

from bench import run as R


def view(trace=True):
    probe = NS(spans=[("schedule", 10, 20), ("schedule", 30, 50), ("drain", 0, 5),
                      ("schedule", 200, 900)],
               rounds=[(15, 10, 100, 1e9, 3), (40, 20, 100, 2e9, 4),
                       (500, 1, 16, 5e9, 1)])
    requests = [NS(handoffs=1, token_ns=[10, 4e6 + 10]),     # 4 ms
                NS(handoffs=1, token_ns=[20, 8e6 + 20]),     # 8 ms
                NS(handoffs=1, token_ns=[30, 9e6 + 30]),     # 9 ms
                NS(handoffs=1, token_ns=[200, 1e9]),         # first token after the window
                NS(handoffs=0, token_ns=[40, 1e9]),          # not handed off
                NS(handoffs=1, token_ns=[50])]               # no second token yet
    return NS(probe=probe, window_ns=(0, 100), requests=requests,
              trace={"busy_s": 1.0, "work_s": 2.0, "idle_work_s": 0.5} if trace else None,
              trace_window_ns=(0, 100), peak={"bf16_flops_per_s": 1e12})


@pytest.mark.parametrize("name,want", [
    ("sched_ms.tail", (10 + 20) / 2 / 1e6),     # two schedule spans in the window
    ("useful_tok_share.tail", 100 * 30 / 200),
    ("step_mfu.sat", 100 * 3e9 / (1.0 * 1e12)),
    ("idle_share.tail", 25.0),
    ("handoff_ms.fleet", 8.0),
])
def test_reader(name, want):
    assert R.reader(name)(view()) == pytest.approx(want)


@pytest.mark.parametrize("name", ["step_mfu.tail", "idle_share.sat"])
def test_trace_readers_read_nothing_without_a_trace(name):
    assert R.reader(name)(view(trace=False)) is None


def test_every_listed_metric_has_a_reader():
    import json
    spec = json.loads((R.ROOT / "BENCHMARK.json").read_text())
    for m in spec["per_layer"]:
        assert callable(R.reader(m["name"]))


def test_fleet_idle_share_is_the_highest_device():
    v = view()
    v.trace["per_device"] = {
        "/device:TPU:0": {"work_s": 2.0, "idle_work_s": 0.2},
        "/device:TPU:1": {"work_s": 1.0, "idle_work_s": 0.3},
        "/device:TPU:2": {"work_s": 0.0, "idle_work_s": 0.0},   # no work: no share
    }
    assert R.reader("idle_share.fleet")(v) == pytest.approx(30.0)


def test_handoff_reads_nothing_without_a_handoff():
    v = view()
    v.requests = [r for r in v.requests if not r.handoffs]
    assert R.reader("handoff_ms.fleet")(v) is None
