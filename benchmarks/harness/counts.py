"""The table of peaks.  The work an ideal chip must do for a model is
counted beside the model's construction, in the configuration's program
file (``configs/<program>.py``)."""
from __future__ import annotations

# Published peaks of one chip, keyed by ``jax.devices()[0].device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB HBM2e at 819 GB/s).
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind):
    """The peaks of ``device_kind``; an unknown device is an error, never
    a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add a "
            "row to benchmarks/harness/counts.py PEAKS with its source"
        ) from None


_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def dtype_bytes(name):
    return _BYTES[name]
