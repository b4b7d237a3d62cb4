"""Cell v51-mesh.shuffle on the CPU at a small size, over four virtual
devices: the sound program is correct and reports the mesh's own metrics,
and the control (MinHash candidates on the same mesh) is not correct;
a set-up that outlasts the mesh_job limit ends the run with exit code 1."""
import os
import subprocess
import sys
from pathlib import Path

import pytest
from conftest import run_subprocess

ROOT = Path(__file__).resolve().parents[2]

CODE = r"""
import copy, json, sys
sys.path.insert(0, %(root)r)
from chipbench import harness
from chipbench.drivers import mesh_job

CELL = "v51-mesh.shuffle"
bench = harness.benchmark()
w = harness.workload(bench, CELL)
assert w["chips"] == 4
cfg = copy.deepcopy(harness.config(w["config"]))
mix = copy.deepcopy(harness.traffic(w["traffic"]))
cfg["world"].update(trajectories=1024, num_places=400, num_types=12,
                    classes_per_type=3)
mix["min_job_s"] = 0.2


def run(traced=False, factory=None):
    return harness.run_cell(bench, CELL, 2 ** 33 + 17, 1.5, traced,
                            require_tpu=False, system_factory=factory,
                            cfg=cfg, mix=mix, log=lambda *a, **k: None)


def control(cfg, forest):
    engine = dict(cfg["engine"], backend="minhash")
    return mesh_job.MeshBatch(dict(cfg, engine=engine), forest)


out = run(traced=True)
assert out["correct"], out["checks"]
assert out["attempted"] >= 1 and out["failed"] == 0, out
assert out["device"]["count"] == 4, out["device"]
want = {"plan_s.mesh", "execute_s.mesh", "results_s.mesh",
        "communities_s.mesh", "compile_s.mesh", "exchange_fill.mesh"}
assert want <= set(out["metrics"]), sorted(out["metrics"])
assert 0 < out["metrics"]["exchange_fill.mesh"]["value"] <= 100
bad = run(factory=control)
assert not bad["correct"], bad["checks"]
print("OK", json.dumps(out["checks"]), json.dumps(bad["checks"]))
"""


def test_mesh_cell_is_correct_and_its_control_is_not():
    out = run_subprocess(CODE % {"root": str(ROOT)}, devices=4)
    assert "OK" in out


STUCK_CODE = r"""
import copy, sys, time
sys.path.insert(0, %(root)r)
from chipbench import harness, system
from chipbench.drivers import mesh_job

CELL = "v51-mesh.shuffle"
bench = harness.benchmark()
w = harness.workload(bench, CELL)
cfg = copy.deepcopy(harness.config(w["config"]))
cfg["world"].update(trajectories=64, num_places=400, num_types=12,
                    classes_per_type=3)
mesh_job.SETUP_LIMIT_S = 2


class Stuck(system.Batch):
    def __init__(self, cfg, forest):
        pass

    def run(self, places, lengths):
        %(body)s


harness.run_cell(bench, CELL, 7, 1.0, False, require_tpu=False,
                 system_factory=Stuck, cfg=cfg, log=lambda *a, **k: None)
print("not stopped")
"""


@pytest.mark.parametrize("body", [
    "time.sleep(120)",                           # waiting, the GIL released
    "t = time.time()\n        while time.time() - t < 120: pass",  # busy
])
def test_mesh_setup_past_its_limit_exits_with_code_1(body):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", STUCK_CODE % {"root": str(ROOT),
                                             "body": body}],
        capture_output=True, text=True, timeout=100, env=env)
    assert proc.returncode == 1, (proc.returncode, proc.stderr[-2000:])
    assert "not stopped" not in proc.stdout
    assert "mesh_job: set-up limit 2 s" in proc.stderr
    assert "set-up ran past its limit of 2 s" in proc.stderr, \
        proc.stderr[-2000:]
    assert "in run" in proc.stderr  # the stack shows where set-up was
