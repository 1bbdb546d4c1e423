"""The trace reduction, on synthetic intervals and on a trace recorded on
a TPU v5e (``data/qwen05-chat-r80.xplane.pb``: 2 s of qwen05-chat-r80,
seven ``jit_step`` rounds, recorded by ``bench/run.py --trace 1``)."""
from pathlib import Path

import pytest

from bench import xplane

TRACE = Path(__file__).resolve().parent / "data" / "qwen05-chat-r80.xplane.pb"


def test_union_overlap_gaps():
    merged = xplane.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert merged == [(0, 3), (5, 9)]
    assert xplane.overlap(merged, 2, 6) == 2
    assert xplane.gaps(merged, -1, 10) == [(-1, 0), (3, 5), (9, 10)]


def test_leaves_drop_containers():
    evs = [("loop", 0, 10), ("a", 1, 4), ("b", 4, 9), ("c", 12, 13)]
    assert [n for n, _, _ in xplane.leaves(evs)] == ["a", "b", "c"]


def test_label():
    hlo = "%fusion.12 = bf16[16,256]{1,0:T(8,128)(2,1)} fusion(bf16[4]{0} %x)"
    assert xplane.label(hlo) == "fusion.12 bf16[16,256]"


def test_idle_gaps_named_by_host_span():
    trace = xplane.DeviceTrace({"/device:TPU:0": [("op", 100, 200), ("op", 300, 400)]},
                               anchor_ns=1000)
    # host clock = trace clock - 1000; work outstanding over all of it
    out = xplane.reduce(trace, (-1000, -500), [(-1000, -500)],
                        [("drain", -850, -790), ("schedule", -760, -700)], 0)
    assert out["busy_s"] == pytest.approx(200e-9)
    assert out["idle_work_s"] == pytest.approx(300e-9)
    assert out["work_s"] == pytest.approx(500e-9)
    # gaps (trace ns) [0, 100], [200, 300], [400, 500]; the schedule span
    # covers 60 ns of the middle one, the drain span 10 ns
    assert sorted(out["idle_gaps"]) == [("host", pytest.approx(1e-7)),
                                        ("host", pytest.approx(1e-7)),
                                        ("schedule", pytest.approx(1e-7))]


def _modules(path):
    """``jit_*`` program executions of the TPU plane, read independently of
    the reduction: (start, end) ns."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    plane = next(p for p in pd.planes if p.name == "/device:TPU:0")
    line = next(ln for ln in plane.lines if ln.name == "XLA Modules")
    return [(int(e.start_ns), int(e.start_ns + e.duration_ns)) for e in line.events
            if e.name.startswith("jit_step")]


def test_recorded_trace_by_hand():
    tr = xplane.load(TRACE)
    steps = _modules(TRACE)
    assert len(steps) == 7
    a, b = steps[0][0], steps[-1][1]
    # the window, on the host clock, is the seven rounds
    host = (a - tr.anchor_ns, b - tr.anchor_ns)
    out = xplane.reduce(tr, host, [host], [], 0)
    step_s = sum(e - s for s, e in steps) / 1e9
    # by hand: the seven rounds read 313.8 + 367.4 + 367.7 + 245.4 + 367.8 +
    # 367.9 + 368.1 ms = 2398.1 ms of program time in a 2445.8 ms span; the
    # ops inside the programs cover all but a few ms of it
    assert step_s == pytest.approx(2.3981, abs=1e-3)
    assert out["window_s"] == pytest.approx(2.4458, abs=1e-3)
    assert 0.98 * step_s <= out["busy_s"] <= step_s + 0.01
    assert out["busy_s"] + out["idle_work_s"] == pytest.approx(out["work_s"])
    ops = dict(out["device_ops"])
    # the oracle's attention over 16 x 256 queries x 8,208 keys leads
    top = max(ops, key=ops.get)
    assert top.startswith("fusion.164 ")
    by_hand = sum(min(e, b) - max(s, a) for n, s, e in tr.ops["/device:TPU:0"]
                  if n.startswith("%fusion.164 ") and e > a and s < b) / 1e9
    assert ops[top] == pytest.approx(by_hand) and by_hand == pytest.approx(0.5741, abs=1e-3)
    assert sum(ops.values()) <= out["busy_s"]


def test_each_device_against_its_own_work():
    """A fleet: each device's idle time counts only while its own replica
    had work, and the lists take each device's largest in turn."""
    trace = xplane.DeviceTrace({
        "/device:TPU:0": [("%a = f32[1]{0} fusion()", 0, 100), ("%b = f32[1]{0} fusion()", 100, 150)],
        "/device:TPU:1": [("%a = f32[1]{0} fusion()", 0, 20)],
    }, anchor_ns=0)
    out = xplane.reduce(trace, (0, 200), {"/device:TPU:0": [(0, 200)],
                                          "/device:TPU:1": [(0, 40)]},
                        [("decode0/drain", 20, 40)], 0)
    assert out["per_device"]["/device:TPU:0"] == pytest.approx(
        {"busy_s": 150e-9, "work_s": 200e-9, "idle_work_s": 50e-9})
    assert out["per_device"]["/device:TPU:1"] == pytest.approx(
        {"busy_s": 20e-9, "work_s": 40e-9, "idle_work_s": 20e-9})
    assert out["busy_s"] == pytest.approx(85e-9)
    assert [n for n, _ in out["device_ops"]] == ["TPU:0 a f32[1]", "TPU:1 a f32[1]",
                                                 "TPU:0 b f32[1]"]
    assert [n for n, _ in out["idle_gaps"]] == ["TPU:0 host", "TPU:1 decode0/drain"]
