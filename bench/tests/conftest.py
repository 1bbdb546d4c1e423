"""Harness self-tests, run by hand on the CPU (the repository's own test
run collects ``tests/`` only):

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

Nothing here loads the TPU's library: every JAX call runs on the CPU."""
import json
import os
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


@pytest.fixture
def tiny(monkeypatch):
    """A cell of the tiny CPU configuration (``data/tiny.json``) under the
    tiny mix, and the arguments that take the place of the chip."""
    import jax

    from bench import generator, modelcfg

    monkeypatch.setattr(modelcfg, "CONFIG_DIR", DATA)
    monkeypatch.setattr(generator, "TRAFFIC_DIR", DATA)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {"name": "tiny-cell", "config": "tiny", "traffic": "tiny_mix",
            "chips": 1, "why": "CPU self-test"}
    spec["workloads"] = [cell]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    return {"cell": (spec, cell), "devices": jax.devices(),
            "peak": {"bf16_flops_per_s": 1e12}}


def tiny_run(tiny, seed=5, seconds=3, **kw):
    from bench import run as R

    args = R.parse(["--workload", "tiny-cell", "--seed", str(seed),
                    "--seconds", str(seconds)])
    return R.run(args, **tiny, **kw)
