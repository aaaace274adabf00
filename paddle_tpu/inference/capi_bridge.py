"""Python side of the C inference API.

``csrc/capi.cc`` embeds CPython and calls these helpers, mirroring the
reference's C binding (``paddle/fluid/inference/capi/pd_predictor.cc``)
over the TPU-native Predictor.  Handles are small ints so the C side never
owns a PyObject* for a predictor; output buffers are returned as ``bytes``
whose lifetime the C side manages by holding the reference until the next
fetch or predictor deletion.
"""
from __future__ import annotations

import numpy as np

_predictors = {}
_next_handle = [1]

# dtype codes shared with csrc/paddle_capi.h (PD_DataType)
_CODE_TO_DTYPE = {
    0: np.float32,
    1: np.int64,
    2: np.int32,
    3: np.uint8,
    4: np.float16,
}
_DTYPE_TO_CODE = {np.dtype(v): k for k, v in _CODE_TO_DTYPE.items()}


def new_predictor(model_path, params_path):
    from . import Config, Predictor

    cfg = Config(model_path or None, params_path or None)
    handle = _next_handle[0]
    _next_handle[0] += 1
    _predictors[handle] = Predictor(cfg)
    return handle


def delete_predictor(handle):
    _predictors.pop(handle, None)


def input_names(handle):
    return list(_predictors[handle].get_input_names())


def output_names(handle):
    return list(_predictors[handle].get_output_names())


def set_input(handle, name, buf, shape, dtype_code):
    dtype = _CODE_TO_DTYPE[int(dtype_code)]
    arr = np.frombuffer(buf, dtype=dtype).reshape([int(s) for s in shape])
    # copy: the caller's buffer is only valid for the duration of this call
    _predictors[handle].get_input_handle(name).copy_from_cpu(arr.copy())


def run(handle):
    _predictors[handle].run()


def get_output(handle, name):
    arr = _predictors[handle].get_output_handle(name).copy_to_cpu()
    arr = np.ascontiguousarray(arr)
    code = _DTYPE_TO_CODE.get(arr.dtype)
    if code is None:  # e.g. bfloat16 / float64 -> widen to float32
        arr = np.ascontiguousarray(arr.astype(np.float32))
        code = 0
    return arr.tobytes(), [int(s) for s in arr.shape], int(code)
