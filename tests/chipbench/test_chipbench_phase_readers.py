"""The readers of the program's own phase spans and compile counter: a
per-job mean over the window's jobs, and silence where a job lacks the
key (a program that does not record it)."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import harness  # noqa: E402

READERS = {"results_s.batch": "t_results",
           "communities_s.batch": "t_communities",
           "compile_s.batch": "compile_s"}


def jobs(key, values):
    return {"jobs": [{"wall_s": 15.0, "stats": {"t_join": 12.0, key: v}}
                     for v in values]}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_is_the_mean_over_jobs(metric):
    read = harness.metric_reader(metric)
    assert read(jobs(READERS[metric], [1.0, 1.5, 0.5, 1.0])) == 1.0
    assert read(jobs(READERS[metric], [0.0, 0.0])) == 0.0


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_is_silent_without_its_key(metric):
    read = harness.metric_reader(metric)
    assert read({"jobs": [{"wall_s": 15.0, "stats": {"t_join": 12.0}}]}) \
        is None
    # one job without it silences the whole window: no mean of a part
    rec = jobs(READERS[metric], [1.0, 2.0])
    del rec["jobs"][1]["stats"][READERS[metric]]
    assert read(rec) is None
    assert read({"jobs": []}) is None and read({}) is None


def test_spans_explain_what_host_result_infers():
    """With the program's spans, the remainder host_result_s.batch reads is
    results plus communities, and nothing else of a job is left out."""
    stats = {"t_encode": 0.01, "t_keys": 0.02, "t_join": 12.0,
             "t_score": 0.85, "t_results": 1.2, "t_communities": 1.1,
             "compile_s": 0.9}
    wall = sum(v for k, v in stats.items() if k.startswith("t_"))
    rec = {"jobs": [{"wall_s": wall, "stats": stats}]}
    rest = harness.metric_reader("host_result_s.batch")(rec)
    spans = sum(harness.metric_reader(m)(rec)
                for m in ("results_s.batch", "communities_s.batch"))
    assert rest == pytest.approx(spans)
    assert harness.metric_reader("compile_s.batch")(rec) == 0.9
