"""Device time by the program's scopes and idle time by its host spans
(``bench/scopes.py``), and the two readers built on them, on synthetic
input and on traces recorded on a TPU v5e:

- ``data/qwen05-chat-r80.xplane.pb``: the program before it had scopes;
- ``data/qwen05-chat-r80.scoped.xplane.pb``: 2 s of qwen05-chat-r80 on the
  program with its recorder on and its device scopes, with
  ``data/qwen05-chat-r80.scoped.spans.json``: the window, the work
  outstanding, the anchor and the program's host spans of that window, as
  ``bench/run.py --trace 1`` with the recorder wired in hands them over."""
import json
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import run as R, scopes, xplane

DATA = Path(__file__).resolve().parent / "data"
TRACE = DATA / "qwen05-chat-r80.xplane.pb"
SCOPED = DATA / "qwen05-chat-r80.scoped.xplane.pb"
SPANS = DATA / "qwen05-chat-r80.scoped.spans.json"
# readings of the scoped trace
ATTN_SHARE = 65.22
IDLE_TOP = ["drain.wait", "block_tables", "dispatch"]


def test_scope_is_the_innermost_program_scope():
    assert scopes.scope("jit(step)/layer_scan/while/body/closed_call/attention/gather:") \
        == "attention"
    assert scopes.scope("jit(step)/while/body/closed_call/ffn/qkv/dot_general:") == "qkv"
    assert scopes.scope("jit(step)/while/body/kv_write/reshape;attention/reshape:") \
        == "attention"
    assert scopes.scope("jit(step)/layer_scan/while/body/dynamic_slice:") == "layer_scan"
    assert scopes.scope("jit(step)/while/body/dynamic_slice:") == "other"
    assert scopes.scope("") == "other"


def test_ops_xla_made_take_their_operands_scope():
    meta = {
        "%fusion.1 = bf16[8] fusion(bf16[8] %p), calls=%c":
            ("jit(step)/layer_scan/while/body/closed_call/attention/gather:", 1),
        "%convert.2 = f32[8] convert(bf16[8] %fusion.1)": ("jit(step)/layer_scan/while:", 1),
        "%slice-done.3 = bf16[4] async-done(%slice-start.4)": ("jit(step)/layer_scan/while:", 1),
        "%slice-start.4 = bf16[4] async-start(bf16[8] %gte.5)": ("jit(step)/layer_scan/while:", 1),
        "%dynamic-slice.6 = bf16[8] dynamic-slice(bf16[8] %gte.5)":
            ("jit(step)/layer_scan/while/body/dynamic_slice:", 1),
        "%add.7 = f32[8] add(f32[8] %convert.2, f32[8] %q)": ("jit(step)/add:", 1),
        "%copy.8 = bf16[8] copy(bf16[8] %gte.9)": ("", 1),
        # the same op names in another program, whose fusion.1 is the scan's
        # own (a norm)
        "%fusion.1 = f32[8] fusion(f32[8] %x), calls=%d":
            ("jit(step)/layer_scan/while/body/closed_call/reduce_sum:", 2),
        "%convert.2 = f32[16] convert(bf16[16] %fusion.1)": ("jit(step)/layer_scan/while:", 2),
    }
    names = list(meta)
    assert scopes.op_scopes(meta) == {
        names[0]: "attention",
        names[1]: "attention",     # a convert of the attention's gather
        names[2]: "layer_scan",    # made by XLA from an op made by XLA too
        names[3]: "layer_scan",
        names[4]: "layer_scan",
        names[5]: "other",         # its only scoped operand was made by XLA
        names[6]: "other",
        names[7]: "layer_scan",
        names[8]: "layer_scan",    # its operand is program 2's fusion.1
    }


def test_device_seconds_by_scope():
    ops = [("loop", 100, 900), ("a", 100, 300), ("b", 300, 600), ("c", 600, 900)]
    trace = xplane.DeviceTrace({"/device:TPU:0": ops}, anchor_ns=0)
    meta = {"a": ("jit(step)/while/body/attention/dot_general:", 1),
            "b": ("jit(step)/while/body/ffn/dot_general:", 1)}     # c has none
    out = scopes.device_s_by_scope(trace, (200, 800), meta, 0)
    assert out == pytest.approx({"attention": 100e-9, "ffn": 300e-9, "other": 200e-9})
    assert list(out) == ["ffn", "other", "attention"]          # largest first


def test_idle_named_by_innermost_program_span():
    trace = xplane.DeviceTrace({"/device:TPU:0": [("op", 0, 100), ("op", 400, 500)]},
                               anchor_ns=0)
    # idle [100, 400): round covers all of it, dispatch [250, 420) inside
    # it and launch [350, 420) inside dispatch
    spans = [("round", 50, 450), ("dispatch", 250, 420), ("launch", 350, 420),
             ("drain.wait", 0, 90)]
    out = scopes.idle_by_span(trace, (0, 600), [(0, 600)], spans, 0)
    assert out == pytest.approx({"round": 150e-9, "dispatch": 100e-9, "launch": 50e-9,
                                 "none": 100e-9})


def test_wire_reader_matches_tensorflow():
    pb2 = pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    # the reader's field numbers are xplane.proto's
    fields = {(m, f): getattr(pb2, m).DESCRIPTOR.fields_by_name[f].number
              for m, f in [("XSpace", "planes"), ("XPlane", "name"),
                           ("XPlane", "event_metadata"), ("XPlane", "stat_metadata"),
                           ("XEventMetadata", "name"), ("XEventMetadata", "stats"),
                           ("XStatMetadata", "name"), ("XStat", "metadata_id"),
                           ("XStat", "uint64_value"), ("XStat", "int64_value"),
                           ("XStat", "str_value"), ("XStat", "ref_value")]}
    assert fields == {
        ("XSpace", "planes"): scopes._SPACE_PLANES,
        ("XPlane", "name"): scopes._PLANE_NAME,
        ("XPlane", "event_metadata"): scopes._PLANE_EVENT_METADATA,
        ("XPlane", "stat_metadata"): scopes._PLANE_STAT_METADATA,
        ("XEventMetadata", "name"): scopes._EVENT_META_NAME,
        ("XEventMetadata", "stats"): scopes._EVENT_META_STATS,
        ("XStatMetadata", "name"): scopes._STAT_META_NAME,
        ("XStat", "metadata_id"): scopes._STAT_METADATA_ID,
        ("XStat", "uint64_value"): scopes._STAT_UINT,
        ("XStat", "int64_value"): scopes._STAT_INT,
        ("XStat", "str_value"): scopes._STAT_STR,
        ("XStat", "ref_value"): scopes._STAT_REF,
    }
    for path in (TRACE, SCOPED):
        space = pb2.XSpace()
        space.ParseFromString(path.read_bytes())
        want = {}       # an op's text can recur in several programs
        for plane in space.planes:
            if not plane.name.startswith("/device:TPU:"):
                continue
            stat_name = {k: v.name for k, v in plane.stat_metadata.items()}
            for meta in plane.event_metadata.values():
                stats = {stat_name.get(st.metadata_id): st for st in meta.stats}
                if "tf_op" not in stats:
                    continue
                tf = stats["tf_op"]
                want.setdefault(meta.name, set()).add((
                    tf.str_value if tf.HasField("str_value") else stat_name.get(tf.ref_value),
                    stats["program_id"].uint64_value if "program_id" in stats else 0))
        got = scopes.op_metadata(path)
        assert want and set(got) == set(want)
        assert all(got[name] in want[name] for name in got)


def test_unscoped_program_reads_other():
    tr = xplane.load(TRACE)
    meta = scopes.op_metadata(TRACE)
    leaf = xplane.leaves(tr.ops["/device:TPU:0"])
    # ops without a name stack (copies and transfers the compiler adds)
    # hold about 3% of the leaf time
    named = sum(e - s for n, s, e in leaf if n in meta)
    assert 0.95 * sum(e - s for _, s, e in leaf) <= named
    window = (leaf[0][1] - tr.anchor_ns, leaf[-1][2] - tr.anchor_ns)
    by_scope = scopes.device_s_by_scope(tr, window, meta, 0)
    assert set(by_scope) == {"other"}
    # so the share reads nothing
    assert R.reader("attn_share.tail")(NS(trace={"device_s_by_scope": by_scope})) is None


def _scoped():
    rec = json.loads(SPANS.read_text())
    return (xplane.load(SCOPED), scopes.op_metadata(SCOPED), tuple(rec["window"]),
            rec["outstanding"], [tuple(s) for s in rec["spans"]], rec["anchor_host_ns"])


def test_scoped_trace_device_seconds_by_scope():
    tr, meta, window, outstanding, _, anchor = _scoped()
    by_scope = scopes.device_s_by_scope(tr, window, meta, anchor)
    total = sum(by_scope.values())
    # the scopes partition the leaf ops of the window
    shift = tr.anchor_ns - anchor
    a, b = window[0] + shift, window[1] + shift
    leaf_s = sum(max(0, min(e, b) - max(s, a))
                 for _, s, e in xplane.leaves(tr.ops["/device:TPU:0"])) / 1e9
    assert total == pytest.approx(leaf_s)
    assert set(scopes.SCOPES) <= set(by_scope)
    assert by_scope["other"] < 0.15 * total
    # the decode oracle's float32 copies of the gathered cache carry the
    # loop's metadata and are the attention's through their operand
    op_scope = scopes.op_scopes(meta)
    converts = [n for n in meta if n.startswith("%convert.") and "f32[4112,16,16,64]" in n]
    assert converts and {op_scope[n] for n in converts} == {"attention"}
    assert {meta[n][0] for n in converts} == {"jit(step)/layer_scan/while:"}
    share = R.reader("attn_share.tail")(NS(trace={"device_s_by_scope": by_scope}))
    assert share == pytest.approx(100 * by_scope["attention"] / total)
    assert share == pytest.approx(ATTN_SHARE, abs=0.05)


def test_scoped_trace_idle_named_by_program_span():
    tr, _, window, outstanding, spans, anchor = _scoped()
    idle = scopes.idle_by_span(tr, window, outstanding, spans, anchor)
    reduced = xplane.reduce(tr, window, outstanding, [], anchor)
    assert sum(idle.values()) == pytest.approx(reduced["idle_work_s"])
    assert set(idle) <= {n for n, _, _ in spans} | {scopes.NO_SPAN}
    # the host stretches the device waits on, largest first
    assert list(idle)[:3] == IDLE_TOP


def view(**kw):
    """A run as a wired harness hands it to the readers: the program's
    spans (``program``) and device seconds by scope in the trace."""
    spans = [("drain.wait", 0, 30, 0, -1), ("launch", 32, 35, 0, -1),
             ("drain.wait", 50, 60, 1, -1), ("schedule", 61, 62, 1, -1),
             ("launch", 62, 63, 1, -1), ("drain.wait", 70, 80, 2, -1),
             ("launch", 88, 90, 2, -1), ("drain.wait", 95, 99, 3, -1),
             ("launch", 100, 110, 3, -1)]
    v = NS(window_ns=(0, 100), trace_window_ns=(0, 40), program=NS(spans=spans),
           trace={"device_s_by_scope": {"attention": 3.0, "ffn": 0.5, "other": 0.5}})
    v.__dict__.update(kw)
    return v


def test_attn_share_reader():
    assert R.reader("attn_share.tail")(view()) == pytest.approx(75.0)
    assert R.reader("attn_share.tail")(view(trace=None)) is None
    assert R.reader("attn_share.tail")(view(trace={"busy_s": 1.0})) is None


def test_host_gap_reader():
    # drain.wait end -> next launch end after the profiler stopped (40):
    # 63 - 60 and 90 - 80 ns; the first round was traced, the last launch
    # ends past the window
    assert R.reader("host_gap_ms.tail")(view()) == pytest.approx((3 + 10) / 2 / 1e6)
    # a harness that does not hand the program over, or a program without
    # a recorder, reads nothing
    assert R.reader("host_gap_ms.tail")(view(program=None)) is None
    v = view()
    del v.program
    assert R.reader("host_gap_ms.tail")(v) is None
