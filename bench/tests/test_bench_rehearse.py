"""Each traffic driver end to end at a tiny size on the CPU: set-up,
window, reference comparison and the result object. The mesh cell runs
in a child process on four virtual CPU devices."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import benchtiny  # noqa: E402


@pytest.mark.parametrize("cell", ["higgs-train", "higgs-serve"])
def test_cell_end_to_end(cell):
    r = benchtiny.execute(cell)
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert "setup_s" in r["metrics"] and len(r["metrics"]) == 2
    assert list(r)[-1] == "checks"
    assert r["device"]["count"] == 1 and "memory_peak_bytes" in r["device"]


MESH = """
import json, sys
sys.path.insert(0, {here!r})
import benchtiny
print(json.dumps(benchtiny.execute("epsilon-train-mesh4")))
"""


def test_mesh_cell_end_to_end_on_four_cpu_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", MESH.format(here=HERE)], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["correct"] is True, r["checks"]
    assert r["device"]["count"] == 4
    assert r["metrics"]["train_rowtrees_per_s"]["value"] > 0
