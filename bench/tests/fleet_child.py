"""One traced run of the tiny configuration as a fleet of one prefill and
three decode replicas on four virtual CPU devices, for ``test_fleet.py``,
which starts it in a process of its own (the device count is fixed when JAX
starts).  Prints the run's result, ``info`` included, as one JSON line.

The CPU's profiler records no device planes, so the trace that ``run.py``
reads is made here from the run itself: on each replica's device plane one
op over each of that replica's ``dispatch`` spans.  Everything else, from
the layout file to the per-device reduction and the readers, is the
harness's own."""
import json
import os
import sys
import time
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


# the per-layer entries a fleet cell lists in BENCHMARK.json
FLEET_METRICS = [
    {"name": "handoff_ms.fleet", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "router", "moves": "itl_p95_ms"},
    {"name": "idle_share.fleet", "unit": "%", "better": "lower",
     "source": "device_trace", "layer": "device", "moves": "itl_p95_ms"},
]


def main() -> None:
    import jax

    from bench import cell as cellmod, generator, modelcfg, run as R, xplane

    modelcfg.CONFIG_DIR = generator.TRAFFIC_DIR = cellmod.CELLS_DIR = DATA
    seen = {}
    instrument = cellmod.instrument

    def capture(system, d, probe):
        seen["system"], seen["probe"] = system, probe
        instrument(system, d, probe)

    class Anchor:
        """``TraceAnnotation`` that keeps the host time of the anchor."""

        def __init__(self, name):
            self.name = name

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            if self.name == xplane.ANCHOR:
                seen["anchor"] = time.perf_counter_ns()

    def load(_path):
        planes = {s.name: f"/device:TPU:{s.engine.device.id}"
                  for s in seen["system"].servers}
        ops = {p: [] for p in planes.values()}
        for name, s, e in seen["probe"].spans:
            replica, _, layer = name.partition("/")
            if layer == "dispatch":
                ops[planes[replica]].append(("%fusion.1 = f32[8]{0} fusion()", s, e))
        return xplane.DeviceTrace(ops, seen["anchor"])

    cellmod.instrument = capture
    jax.profiler.TraceAnnotation = Anchor
    xplane.load = load
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {"name": "tiny-fleet", "config": "tiny", "traffic": "tiny_mix",
            "chips": 4, "why": "CPU self-test of a fleet"}
    spec["workloads"] = [cell]
    spec["per_layer"] += FLEET_METRICS
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    args = R.parse(["--workload", "tiny-fleet", "--seed", sys.argv[1],
                    "--seconds", "3", "--trace", "1"])
    res = R.run(args, cell=(spec, cell), devices=jax.devices(),
                peak={"bf16_flops_per_s": 1e12})
    print(json.dumps(res))


if __name__ == "__main__":
    main()
