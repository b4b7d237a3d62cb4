"""Cell v51-batch.jobs on the CPU at a small size: the sound program is correct, and
the control and every fault the cell can have are not."""
import pytest

from chipbench_small import run


def test_sound_program_is_correct_and_reports_its_metrics():
    out = run("v51-batch.jobs")
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert "setup_s" in out["metrics"]
    assert list(out)[-1] == "checks"


def test_control_with_approximate_candidates_is_not_correct():
    from chipbench import faults

    out = run("v51-batch.jobs", factory=faults.control)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", ["stale", "half", "altered"])
def test_fault_in_the_timed_path_is_not_correct(fault):
    from chipbench import faults

    out = run("v51-batch.jobs", factory=faults.faulty(fault))
    assert not out["correct"], (fault, out["checks"])
