"""Job-level fault recovery end-to-end (§5.3): a training process is
SIGKILL-analogue-murdered mid-job, relaunched, resumes from the last
auto-checkpoint, and finishes with EXACTLY the weights of an
uninterrupted run (reference: incubate auto_checkpoint's
train_epoch_range contract)."""
import os
import subprocess
import sys

import numpy as np
import pytest

pytestmark = pytest.mark.slow

WORKER = os.path.join(os.path.dirname(__file__),
                      "fault_recovery_worker.py")


def _run(tmp, ckpt_name, out_name, kill_after=-1):
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               PADDLE_RUNNING_ENV="PADDLE_EDL_AUTO_CHECKPOINT",
               PADDLE_CHECKPOINT_DIR=str(tmp / ckpt_name),
               OUT_PATH=str(tmp / out_name),
               KILL_AFTER_EPOCH=str(kill_after))
    return subprocess.run([sys.executable, WORKER], env=env,
                          capture_output=True, text=True, timeout=300)


def test_kill_and_resume_matches_clean_run(tmp_path):
    # clean reference run
    clean = _run(tmp_path, "ck_clean", "clean.npz")
    assert clean.returncode == 0, clean.stdout + clean.stderr
    assert "DONE" in clean.stdout

    # killed mid-job (dies before epoch 3's snapshot lands)
    killed = _run(tmp_path, "ck_fault", "fault.npz", kill_after=3)
    assert killed.returncode == 137
    assert "EPOCH 3" in killed.stdout
    assert not (tmp_path / "fault.npz").exists()

    # relaunch: resumes at epoch 3 (last snapshot = epoch 2), finishes
    resumed = _run(tmp_path, "ck_fault", "fault.npz")
    assert resumed.returncode == 0, resumed.stdout + resumed.stderr
    first_line = resumed.stdout.splitlines()[0]
    assert first_line.startswith("EPOCH 3"), resumed.stdout

    a = np.load(tmp_path / "clean.npz")
    b = np.load(tmp_path / "fault.npz")
    assert sorted(a.files) == sorted(b.files)
    for k in a.files:
        np.testing.assert_allclose(a[k], b[k], rtol=1e-6, atol=1e-7,
                                   err_msg=k)
