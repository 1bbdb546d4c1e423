"""A fleet cell on the CPU: the tiny configuration as one prefill and three
decode replicas (``data/tiny-fleet.json``) on four virtual devices, one
traced run through ``bench/run.py``'s own path (``fleet_child.py``, in a
process of its own since the device count is fixed when JAX starts)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

CHILD = Path(__file__).resolve().parent / "fleet_child.py"


@pytest.fixture(scope="module")
def fleet_run():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(CHILD), str(2**31 + 13)], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_fleet_is_correct(fleet_run):
    assert fleet_run["correct"], fleet_run["checks"]
    assert fleet_run["failed"] == 0
    assert fleet_run["info"]["compared"]["tokens"] >= 100


def test_every_request_handed_off(fleet_run):
    fleet = fleet_run["info"]["fleet"]
    assert fleet["handoffs"]["colocated"] == 0
    assert fleet["handoffs"]["dropped"] == 0
    assert fleet["requests_handed_off"] == fleet["requests_with_first_token"] > 0
    assert fleet["handoffs"]["delivered"] == fleet["requests_handed_off"]
    assert all(r["rounds_in_window"] > 0 for r in fleet["replicas"])


def test_replicas_on_four_devices(fleet_run):
    replicas = fleet_run["info"]["fleet"]["replicas"]
    assert [r["name"] for r in replicas] == ["prefill0", "decode0", "decode1", "decode2"]
    assert len({r["device"] for r in replicas}) == 4
    assert fleet_run["device"]["count"] == 4


def test_fleet_metrics_read_from_the_run(fleet_run):
    metrics = fleet_run["metrics"]
    assert metrics["handoff_ms.fleet"]["value"] > 0
    per_device = fleet_run["info"]["trace"]["per_device"]
    assert len(per_device) == 4
    shares = [100 * v["idle_work_s"] / v["work_s"] for v in per_device.values()]
    assert metrics["idle_share.fleet"]["value"] == pytest.approx(max(shares))
    # the breakdown takes each device's largest in turn
    tags = {name.split()[0] for name, _ in fleet_run["breakdown"]["device_ops"]}
    assert tags == {f"TPU:{i}" for i in range(4)}
