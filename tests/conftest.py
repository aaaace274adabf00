"""Test env: force CPU backend with 8 virtual devices BEFORE jax loads.

This is the reference's multi-process-on-localhost pattern (SURVEY.md §4)
mapped to TPU testing: a virtual 8-device mesh exercises every sharding
path without hardware.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
prev = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (
        prev + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import jax  # noqa: E402

assert jax.default_backend() == "cpu", jax.devices()
assert len(jax.devices()) == 8, jax.devices()

# Persistent compile cache: the suite compiles the same tiny-model HLO
# hundreds of times across files (fresh Python objects defeat the
# in-process jit cache, but the HLO hash matches).  Measured 5.03s ->
# 1.08s per repeated tiny-GPT TrainStep compile; keyed on HLO so code
# changes invalidate naturally.  The directory is the program's own
# (core/compile_cache.py); only the thresholds are the suite's, so that
# its many sub-second programs are kept too.
from paddle_tpu.core.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed_all():
    import paddle_tpu
    paddle_tpu.seed(102)
    yield


def pytest_collection_modifyitems(config, items):
    """Skip tests listed in tools/flaky_quarantine.txt (reference parity:
    tools/get_quick_disable_lt.py flaky quarantine), and gate
    mesh-marked tests on the device pool: mp/dp-sharded serving needs
    >= 4 devices, which the XLA_FLAGS forcing above provides — but a
    caller-set XLA_FLAGS (respected, line 12) may provide fewer, and
    those tests must SKIP loudly rather than fail on mesh build."""
    if len(jax.devices()) < 4:
        mesh_skip = pytest.mark.skip(
            reason=f"mesh tests need >= 4 devices, have "
                   f"{len(jax.devices())} — force a virtual pool via "
                   "XLA_FLAGS=--xla_force_host_platform_device_count")
        for item in items:
            if "mesh" in item.keywords:
                item.add_marker(mesh_skip)
    qpath = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "flaky_quarantine.txt")
    if not os.path.exists(qpath):
        return
    with open(qpath) as f:
        quarantined = {line.strip() for line in f
                       if line.strip() and not line.startswith("#")}
    if not quarantined:
        return
    marker = pytest.mark.skip(reason="quarantined-flaky (tools/"
                              "flaky_quarantine.txt)")
    for item in items:
        if item.nodeid in quarantined or \
                item.nodeid.split("::")[0] in quarantined:
            item.add_marker(marker)
