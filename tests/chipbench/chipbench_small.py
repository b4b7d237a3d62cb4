"""The benchmark's cells cut to a size the CPU runs in seconds, driven
through the whole harness with the look for a chip skipped."""
import copy
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import harness  # noqa: E402

# every configuration and traffic mix under chipbench/, as cells named
# <config>.<traffic>, whether or not BENCHMARK.json runs them yet
CELLS = [{"name": "v51-batch.jobs", "config": "v51-batch", "traffic": "jobs",
          "chips": 1},
         {"name": "v51-resident.serve", "config": "v51-resident",
          "traffic": "serve", "chips": 1},
         {"name": "v51-resident.ingest", "config": "v51-resident",
          "traffic": "ingest", "chips": 1}]
BENCH = dict(harness.benchmark(ROOT), workloads=CELLS)


def small(cell: str):
    """(cfg, mix) of ``cell`` at a CPU size: a dense world (few types, so
    many pairs are similar), small updates and slow arrivals."""
    w = harness.workload(BENCH, cell)
    cfg = copy.deepcopy(harness.config(w["config"]))
    mix = copy.deepcopy(harness.traffic(w["traffic"]))
    cfg["world"].update(trajectories=1024, num_places=400, num_types=12,
                        classes_per_type=3)
    if cfg["system"] == "resident":
        cfg["update_rows"] = 64
        cfg["stream"] = dict(window=6, world_capacity=1024,
                             join_slab_capacity=1024 * 64)
    if mix["entry"] == "job":
        mix["min_job_s"] = 0.2
    elif mix["entry"] == "query":
        mix.update(warm_updates=6, rate_per_s=100, max_batch=16,
                   warm_batches=1, check_sample=48, trace_s=0.3)
    else:
        mix.update(warm_updates=8, max_updates=6, traced_updates=[1, 3])
    return cfg, mix


def run(cell: str, seed: int = 2 ** 33 + 17, seconds: float = 1.5,
        factory=None, traced: bool = False):
    cfg, mix = small(cell)
    return harness.run_cell(BENCH, cell, seed, seconds, traced,
                            require_tpu=False, system_factory=factory,
                            cfg=cfg, mix=mix, log=lambda *a, **k: None)
