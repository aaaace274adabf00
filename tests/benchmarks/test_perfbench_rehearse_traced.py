"""``--rehearse --trace 1`` for every cell: the per-layer metrics each
cell's files can read without a device."""
import pytest

from bench_paths import run_cell
from test_perfbench_rehearse import CELLS, check_line

# what needs the device trace reads nothing on the CPU and is left out
NEEDS_DEVICE = ("roofline_share.", "dev_share.")


@pytest.mark.parametrize("workload", CELLS)
def test_traced_rehearsal(workload):
    rc, lines, err = run_cell(workload, "--rehearse", trace=1, seed=23)
    assert rc == 0, err[-2000:]
    res, wanted = check_line(lines, workload, "per_layer")
    missing = wanted - set(res["metrics"])
    assert all(n.startswith(NEEDS_DEVICE) for n in missing), missing
    dev = res["device"]
    assert {"busy_s", "window_s", "host_window_s", "extent_s"} <= set(dev)
    # (the CPU has no device plane: busy and extent read 0 here, and the
    # window is the host's)
    assert 0 <= dev["busy_s"] <= dev["extent_s"] <= dev["window_s"]
    assert dev["window_s"] == max(dev["host_window_s"], dev["extent_s"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    for name in res["metrics"]:
        if name.startswith("compiles_in_window"):
            assert res["metrics"][name]["value"] == 0
