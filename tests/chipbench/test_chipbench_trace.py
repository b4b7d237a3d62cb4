"""The reduction from a profiler trace to the per-layer numbers, on a small
hand-made trace (times in nanoseconds)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import trace  # noqa: E402


def test_union_merges_overlaps_and_clips_to_the_window():
    iv = [(0, 10), (5, 20), (30, 40), (35, 36), (90, 120)]
    assert trace.union_ns(iv, 0, 100) == 20 + 10 + 10
    assert trace.union_ns(iv, 8, 32) == 12 + 2
    assert trace.union_ns([], 0, 100) == 0


def test_gaps_are_the_uncovered_stretches():
    iv = [(10, 20), (15, 25), (40, 50)]
    assert trace.gaps_ns(iv, 0, 60) == [(0, 10), (25, 40), (50, 60)]
    assert trace.gaps_ns(iv, 12, 45) == [(25, 40)]
    assert trace.gaps_ns([(0, 100)], 10, 90) == []


def test_reduce_events_busy_kernel_ops_and_idle_by_host_span():
    ops = [
        ("jit_ssh/fusion.1", 100, 100),     # 100-200
        ("jit_score/lcs_lanes.3", 250, 300),  # 250-550
        ("jit_score/lcs_lanes.3", 500, 100),  # 500-600, overlaps the last
        ("jit_cc/sort.2", 900, 50),         # 900-950
    ]
    spans = [
        ("job", 0, 1000),
        ("phase.score", 200, 400),          # 200-600
        ("phase.communities", 600, 400),    # 600-1000
    ]
    out = trace.reduce_events([ops], spans, (0, 1000))
    # busy: 100 + (250..600 = 350) + 50
    assert out["busy_s"] == 500e-9
    assert out["window_s"] == 1000e-9
    assert out["kernel_ns"] == 400
    assert out["device_ops"][0] == ["jit_score/lcs_lanes.3", 400e-9]
    # idle: 0-100 (job), 200-250 (phase.score), 600-900 and 950-1000
    # (phase.communities): the gap goes to the span covering most of it,
    # the shorter span on a tie
    idle = dict(out["idle_gaps"])
    assert idle["phase.communities"] == 350e-9
    assert idle["job"] == 100e-9
    assert idle["phase.score"] == 50e-9
    assert [k for k, _ in out["idle_gaps"]] == [
        "phase.communities", "job", "phase.score"]


def test_reduce_events_averages_over_devices_and_counts_a_gap_with_no_span():
    a = [("fusion", 0, 50)]
    b = [("fusion", 0, 100)]
    out = trace.reduce_events([a, b], [], (0, 100))
    assert out["busy_s"] == 75e-9
    assert out["kernel_ns"] == 0
    assert out["idle_gaps"] == [["(no span)", 25e-9]]


def test_nested_ops_count_their_own_time_and_the_kernel_by_its_op_name():
    ops = [
        ("jit_score/while.3", 0, 100),          # a loop over two chunks
        ("jit_score/lcs_lanes.1", 10, 30),
        ("jit_score/lcs_lanes.1", 50, 30),
        ("jit_score/fusion.7", 85, 10),
        ("jit_other/lcs_lanes_x", 120, 10),     # not the kernel
    ]
    out = trace.reduce_events([ops], [], (0, 200))
    own = dict(out["device_ops"])
    assert own["jit_score/lcs_lanes.1"] == 60e-9
    assert own["jit_score/while.3"] == 30e-9
    assert own["jit_score/fusion.7"] == 10e-9
    assert out["kernel_ns"] == 60
    assert out["busy_s"] == 110e-9
