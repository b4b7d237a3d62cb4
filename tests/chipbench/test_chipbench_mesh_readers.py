"""The readers of cell v51-mesh.shuffle: each one's arithmetic on made-up
records, and silence where a record lacks what it reads (a program that
does not count it)."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import harness  # noqa: E402

SPANS = {"plan_s.mesh": "t_plan", "execute_s.mesh": "t_execute",
         "results_s.mesh": "t_results",
         "communities_s.mesh": "t_communities",
         "compile_s.mesh": "compile_s"}


def jobs(**per_job):
    """Records of len(values) jobs, job j with stats {key: values[j]}."""
    n = len(next(iter(per_job.values())))
    return {"jobs": [{"wall_s": 9.0,
                      "stats": {k: v[j] for k, v in per_job.items()}}
                     for j in range(n)]}


@pytest.mark.parametrize("metric", sorted(SPANS))
def test_span_reader_is_the_mean_over_jobs(metric):
    read = harness.metric_reader(metric)
    assert read(jobs(**{SPANS[metric]: [0.5, 1.5, 2.5, 1.5]})) == 1.5


@pytest.mark.parametrize("metric", sorted(SPANS))
def test_span_reader_is_silent_without_its_key(metric):
    read = harness.metric_reader(metric)
    rec = jobs(**{SPANS[metric]: [1.0, 2.0]})
    del rec["jobs"][1]["stats"][SPANS[metric]]
    assert read(rec) is None
    assert read({"jobs": []}) is None and read({}) is None


def test_exchange_fill_is_rows_over_slots_of_all_rounds():
    read = harness.metric_reader("exchange_fill.mesh")
    a = {"key_route": [30, 100], "dedup_route": [50, 100],
         "owner_hop1": [10, 50], "owner_hop2": [10, 50]}   # 100 / 300
    b = {"key_route": [60, 100], "dedup_route": [40, 100]}  # 100 / 200
    got = read(jobs(exchange=[a, b]))
    assert got == pytest.approx((100 / 3 + 50) / 2)


def test_exchange_fill_is_silent_without_counters():
    read = harness.metric_reader("exchange_fill.mesh")
    assert read(jobs(t_plan=[1.0])) is None
    assert read(jobs(exchange=[{}])) is None
    assert read({}) is None


def test_idle_share_is_the_chips_mean_idle_of_the_window():
    read = harness.metric_reader("idle_share.mesh")
    # the trace reduction already averages busy time over the devices
    assert read({"trace": {"busy_s": 3.0, "window_s": 12.0}}) == 75.0
    assert read({"trace": None}) is None and read({}) is None


def test_lcs_ns_per_pair_is_per_chip():
    read = harness.metric_reader("lcs_ns_per_pair.mesh")
    rec = jobs(n_shards=[4, 4])
    rec.update(trace={"kernel_ns": 6.0e9}, traced_pairs=8_000_000)
    # 6 s of kernel on each chip over its 2,000,000 pairs
    assert read(rec) == pytest.approx(3000.0)


def test_lcs_ns_per_pair_is_silent_without_its_inputs():
    read = harness.metric_reader("lcs_ns_per_pair.mesh")
    rec = jobs(t_plan=[1.0])            # no n_shards: a program without it
    rec.update(trace={"kernel_ns": 6.0e9}, traced_pairs=8_000_000)
    assert read(rec) is None
    rec = jobs(n_shards=[4])
    rec.update(trace={"kernel_ns": 0}, traced_pairs=8_000_000)
    assert read(rec) is None
    assert read({}) is None
