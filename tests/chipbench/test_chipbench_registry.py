"""The metric arithmetic, and that every piece of a cell is found by name."""
import json
import math
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import harness  # noqa: E402

BENCH = harness.benchmark(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def read(name, rec):
    return harness.metric_reader(name)(rec)


def test_p95_is_nearest_rank_over_every_request():
    lat = list(range(1, 101))
    assert harness.percentile(lat, 0.95) == 95
    assert harness.percentile([7.0], 0.95) == 7.0
    # a request that never came back counts as missing every limit
    assert harness.percentile([1.0] * 19 + [math.inf], 0.95) == 1.0
    assert math.isinf(harness.percentile([1.0] * 18 + [math.inf] * 2, 0.95))
    assert read("query_p95_ms", {"latency_ms": lat}) == 95


def test_rates_and_times_per_job_take_the_whole_window():
    rec = {"window_s": 50.0, "jobs": [{"wall_s": 10.0, "stats": {}}] * 4}
    assert read("job_s", rec) == 12.5
    assert read("job_s", {"window_s": 50.0, "jobs": []}) is None
    rec = {"window_s": 50.0, "rows": 409600, "update_ms": [400.0] * 100}
    assert read("ingest_rows_per_s", rec) == 8192.0
    assert read("setup_s", {"setup_s": 31.5}) == 31.5


def test_per_job_phase_means():
    jobs = [{"wall_s": 10.0, "stats": {"t_encode": 0.5, "t_keys": 0.5,
                                       "t_join": 6.0, "t_score": 1.0}},
            {"wall_s": 12.0, "stats": {"t_encode": 0.5, "t_keys": 0.5,
                                       "t_join": 8.0, "t_score": 1.0}}]
    rec = {"jobs": jobs}
    assert read("join_s.batch", rec) == 7.0
    assert read("score_s.batch", rec) == 1.0
    assert read("host_result_s.batch", rec) == 2.0


def test_trace_metrics_and_silence_without_a_trace():
    rec = {"trace": {"busy_s": 3.0, "window_s": 4.0, "kernel_ns": 5e8},
           "traced_pairs": 10 ** 7}
    assert read("idle_share.batch", rec) == 25.0
    assert read("lcs_ns_per_pair.batch", rec) == 50.0
    for name in ("idle_share.batch", "idle_share.serve", "lcs_ns_per_pair.batch"):
        assert read(name, {}) is None
    no_kernel = {"trace": dict(rec["trace"], kernel_ns=0), "traced_pairs": 1}
    assert read("lcs_ns_per_pair.batch", no_kernel) is None


def test_serving_and_ingest_readers():
    assert read("batch_ms.serve", {"batch_ms": [3.0, 1.0, 2.0]}) == 2.0
    assert read("candidates_per_query.serve",
                {"candidates": 500, "queries_served": 100}) == 5.0
    rec = {"update_ms": list(range(1, 21)), "expire_s": [0.5, 1.5]}
    assert read("update_p95_ms.ingest", rec) == 19
    assert read("retire_ms.ingest", rec) == 1000.0
    assert read("retire_ms.ingest", {}) is None


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_has_its_reader_and_cells(metric):
    assert NAME.match(metric["name"])
    assert callable(harness.metric_reader(metric["name"]))
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if "moves" in metric:
        moved = {m["name"]: m for m in BENCH["end_to_end"]}[metric["moves"]]
        assert set(metric["workloads"]) <= set(
            moved.get("workloads", cells))


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_finds_its_config_traffic_and_driver(cell):
    cfg = harness.config(cell["config"])
    mix = harness.traffic(cell["traffic"])
    drv = harness.driver(mix["entry"])
    assert all(callable(getattr(drv, f)) for f in ("setup", "window", "check"))
    assert cfg["name"] == cell["config"]
    entry = {c["name"]: c for c in BENCH["configs"]}[cell["config"]]
    assert (ROOT / entry["file"]).resolve() == \
        (ROOT / "chipbench/configs" / f"{cell['config']}.json").resolve()
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    ends = harness.metrics_of(BENCH, cell["name"], traced=False)
    assert "setup_s" in {m["name"] for m in ends} and len(ends) >= 2
    assert harness.metrics_of(BENCH, cell["name"], traced=True)


def test_benchmark_names_and_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(m["bound"] <= 0.25 for m in BENCH["end_to_end"])
    assert (ROOT / BENCH["command"][1]).is_file()
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_peaks_table_names_the_chip():
    peaks = harness.load_json(ROOT / "chipbench" / "peaks.json")
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
