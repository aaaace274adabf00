"""Every examples/*.py script runs end-to-end as a subprocess
(reference parity: tests/book/ ran the documented end-to-end models)."""
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(ROOT, "examples")

SCRIPTS = [
    "train_mnist.py",
    "static_graph.py",
    "ps_embedding.py",
    "generate_text.py",
    "train_gpt2.py",
    "distributed_hybrid.py",
    "pipeline_1f1b.py",
    "ragged_text_buckets.py",
    "quant_aware_training.py",
    "packed_pretraining.py",
    "serving_decode.py",
    "serving_engine.py",
    "serving_router.py",
    "serving_disaggregated.py",
    "serving_sharded.py",
    "serving_selfhealing.py",
    "geo_async_ps.py",
    "onnx_export.py",
    "serving_quantized.py",
    "serving_lora.py",
    "serving_offload.py",
]


@pytest.mark.parametrize("script", SCRIPTS)
def test_example_runs(script):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        PADDLE_TPU_SYNTH_N="96",
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, script)],
        capture_output=True, text=True, timeout=900, env=env, cwd=ROOT)
    assert proc.returncode == 0, (
        f"{script} failed:\n{proc.stderr[-2000:]}")
