import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np

from repro.core.types import PAD_ID, TrajectoryBatch

# Tests run on the default single CPU device; multi-device tests spawn
# subprocesses with XLA_FLAGS (the device count binds at jax init).
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def run_subprocess(code: str, devices: int = 8, timeout: int = 900):
    """Run a python snippet under a forced host-device count."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed:\nSTDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
        )
    return proc.stdout


def make_batch(places, lengths):
    """A TrajectoryBatch of the given rows, user ids 0..n-1."""
    return TrajectoryBatch(
        places=jnp.asarray(np.asarray(places, np.int32)),
        lengths=jnp.asarray(np.asarray(lengths, np.int32)),
        user_id=jnp.arange(np.asarray(places).shape[0], dtype=jnp.int32),
    )


def score_map(res):
    """``{(left, right): (mss, level LCS)}`` of a result's scored pairs."""
    left = np.asarray(res.scored.left)
    right = np.asarray(res.scored.right)
    mss = np.asarray(res.scored.mss)
    lvl = np.asarray(res.scored.level_lcs)
    keep = left != PAD_ID
    return {
        (int(a), int(b)): (float(m), tuple(int(x) for x in lv))
        for a, b, m, lv in zip(left[keep], right[keep], mss[keep], lvl[keep])
    }
