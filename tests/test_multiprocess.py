"""Multi-process distributed validation on localhost (reference §4:
test_collective_base.py spawns 2 ranks with real transports over loopback;
here 2 jax processes over the gRPC coordinator)."""
import os
import socket
import subprocess
import sys
import time

import pytest

pytestmark = pytest.mark.slow

LAUNCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "paddle_tpu", "distributed", "launch.py")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _launcher_env(ndev_per_proc=2):
    """Env for launcher-driven CPU multi-process runs."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={ndev_per_proc}"
    for k in ("PADDLE_TRAINERS_NUM", "PADDLE_TRAINER_ID",
              "PADDLE_TRAINER_ENDPOINTS"):
        env.pop(k, None)
    return env


def _extract(out, tag):
    for line in out.splitlines():
        if line.startswith(f"RESULT {tag} "):
            return line.split(" ", 3)[3]
    raise AssertionError(f"missing {tag}:\n{out[-2000:]}")


def test_two_process_psum_and_dp_training():
    worker = os.path.join(os.path.dirname(__file__),
                          "multiprocess_worker.py")
    port = str(_free_port())
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    procs = [subprocess.Popen(
        [sys.executable, worker, str(r), port],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env) for r in range(2)]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
        outs.append(out)
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out[-3000:]}"
        assert f"RESULT done {r}" in out, out[-2000:]
    # cross-rank consistency: identical psum and identical loss curves
    def extract(out, tag):
        for line in out.splitlines():
            if line.startswith(f"RESULT {tag} "):
                return line.split(" ", 3)[3]
        raise AssertionError(f"missing {tag}:\n{out[-2000:]}")

    assert extract(outs[0], "psum") == extract(outs[1], "psum")
    l0 = [float(v) for v in extract(outs[0], "losses").split(",")]
    l1 = [float(v) for v in extract(outs[1], "losses").split(",")]
    assert l0 == pytest.approx(l1, rel=1e-5)   # same global computation
    assert l0[-1] < l0[0]                      # and it actually trains
    # multi-host pipeline (pp spans the two processes), both schedules;
    # the two schedules must also agree with each other
    p0 = [float(v) for v in extract(outs[0], "pp_gpipe").split(",")]
    p1 = [float(v) for v in extract(outs[1], "pp_gpipe").split(",")]
    f0 = [float(v) for v in extract(outs[0], "pp_1f1b").split(",")]
    f1 = [float(v) for v in extract(outs[1], "pp_1f1b").split(",")]
    assert p0 == pytest.approx(p1, rel=1e-5)
    assert f0 == pytest.approx(f1, rel=1e-5)
    assert f0 == pytest.approx(p0, rel=1e-3, abs=1e-4)
    assert p0[-1] < p0[0]


def test_launcher_fsdp_tp_parity(tmp_path):
    """The launcher's --nproc_per_node mode runs the FSDP (ZeRO-2) and
    TP worker across 2 real processes; losses must match the same
    worker run single-process (reference test_dist_base.py:668
    pattern: identical script, world 1 vs N, compare losses)."""
    worker = os.path.join(os.path.dirname(__file__),
                          "multiprocess_worker_fsdp.py")
    env = _launcher_env()
    # 2-process run via the launcher (workerlog.N files)
    log_dir = str(tmp_path / "logs")
    rc = subprocess.run(
        [sys.executable, LAUNCH, "--nproc_per_node", "2",
         "--log_dir", log_dir, worker],
        env=env, timeout=420).returncode
    outs = []
    for r in range(2):
        with open(os.path.join(log_dir, f"workerlog.{r}")) as f:
            outs.append(f.read())
    assert rc == 0, f"launcher failed:\n{outs[0][-2000:]}\n{outs[1][-2000:]}"
    # single-process reference (same script, same seeds, 2 local devices)
    ref = subprocess.run([sys.executable, worker], env=env,
                         capture_output=True, text=True, timeout=420)
    assert ref.returncode == 0, ref.stdout[-2000:] + ref.stderr[-2000:]
    for tag in ("fsdp", "tp"):
        l0 = [float(v) for v in _extract(outs[0], tag).split(",")]
        l1 = [float(v) for v in _extract(outs[1], tag).split(",")]
        lr = [float(v) for v in _extract(ref.stdout, tag).split(",")]
        # both ranks see the same global loss...
        assert l0 == pytest.approx(l1, rel=1e-5), tag
        # ...and it equals the single-process run (same global math)
        assert l0 == pytest.approx(lr, rel=1e-4, abs=1e-6), tag
        assert l0[-1] < l0[0], tag


def test_launcher_abort_all():
    """Reference launch_utils.py:526 watch loop: one failed worker
    aborts the rest; the launcher exits promptly with the failing
    worker's code instead of waiting out the survivors."""
    worker = os.path.join(os.path.dirname(__file__),
                          "multiprocess_worker_abort.py")
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, LAUNCH, "--nproc_per_node", "2", worker],
        env=_launcher_env(), capture_output=True, text=True, timeout=90)
    elapsed = time.monotonic() - t0
    assert proc.returncode == 7, (proc.returncode, proc.stderr[-1000:])
    assert "aborting all workers" in proc.stderr
    # rank 0 sleeps 120s; finishing well under that proves the abort
    assert elapsed < 60, elapsed
