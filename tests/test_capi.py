"""End-to-end test of the C inference API (csrc/capi.cc).

Mirrors the reference's capi tests (paddle/fluid/inference/capi/ used from
inference/tests/api/analyzer_capi_tester.cc): export a model, drive it
through the pure-C surface — here by compiling a real C program against
paddle_capi.h and checking its output against the Python Predictor.
"""
import json
import os
import subprocess

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CSRC = os.path.join(ROOT, "paddle_tpu", "csrc")
LIB = os.path.join(CSRC, "libpaddle_capi.so")

C_DRIVER = r"""
#include <stdio.h>
#include <stdlib.h>
#include "paddle_capi.h"

int main(int argc, char** argv) {
  PD_Config* cfg = PD_NewConfig();
  PD_ConfigSetModel(cfg, argv[1], NULL);
  PD_Predictor* pred = PD_NewPredictor(cfg);
  if (!pred) { fprintf(stderr, "new: %s\n", PD_LastError()); return 2; }

  float in[8];
  for (int i = 0; i < 8; ++i) in[i] = (float)i * 0.5f - 2.0f;
  int64_t shape[2] = {2, 4};
  const char* in_name = PD_GetInputName(pred, 0);
  if (PD_SetInput(pred, in_name, in, shape, 2, PD_FLOAT32)) {
    fprintf(stderr, "set: %s\n", PD_LastError()); return 3;
  }
  if (PD_Run(pred)) { fprintf(stderr, "run: %s\n", PD_LastError()); return 4; }

  const void* data; const int64_t* oshape; int ndim; PD_DataType dt;
  const char* out_name = PD_GetOutputName(pred, 0);
  if (PD_GetOutput(pred, out_name, &data, &oshape, &ndim, &dt)) {
    fprintf(stderr, "get: %s\n", PD_LastError()); return 5;
  }
  printf("{\"ndim\": %d, \"dtype\": %d, \"shape\": [", ndim, (int)dt);
  long total = 1;
  for (int i = 0; i < ndim; ++i) {
    printf(i ? ",%lld" : "%lld", (long long)oshape[i]);
    total *= oshape[i];
  }
  printf("], \"values\": [");
  const float* f = (const float*)data;
  for (long i = 0; i < total; ++i) printf(i ? ",%.6f" : "%.6f", f[i]);
  printf("]}\n");
  PD_DeletePredictor(pred);
  PD_DeleteConfig(cfg);
  return 0;
}
"""


def _build_lib():
    # Always invoke make: its mtime rules rebuild when capi.cc or
    # paddle_capi.h changed, so the suite never runs against a stale
    # committed binary (a no-op when up to date).
    try:
        subprocess.run(["make", "-C", CSRC, "capi"], check=True,
                       capture_output=True, timeout=180)
    except Exception:
        return False
    return os.path.exists(LIB)


def test_so_matches_sources():
    """The committed .so must embed the hash of the checked-out sources.

    Guards against editing capi.cc without rebuilding: make's mtime rules
    catch a newer source, and this hash check catches the remaining case
    (fresh checkout where mtimes are unordered but the binary is old).
    Deliberately NOT skipped when the build fails — a broken native
    build is a failure, not an environment quirk."""
    import ctypes
    assert _build_lib(), "libpaddle_capi.so failed to build"
    from paddle_tpu.csrc import source_hash
    lib = ctypes.CDLL(LIB)
    assert hasattr(lib, "PD_SourceHash"), \
        "stale libpaddle_capi.so: predates source-hash embedding"
    fn = lib.PD_SourceHash
    fn.restype = ctypes.c_char_p
    assert fn().decode() == source_hash("capi.cc", "paddle_capi.h"), \
        ("libpaddle_capi.so is stale: rebuild with "
         "make -B -C paddle_tpu/csrc capi")


@pytest.fixture(scope="module")
def exported_model(tmp_path_factory):
    d = tmp_path_factory.mktemp("capi_model")
    path = str(d / "linear")
    paddle.seed(7)
    layer = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 3))
    layer.eval()
    from paddle_tpu import jit as jit_mod
    from paddle_tpu.static import InputSpec
    jit_mod.save(layer, path,
                 input_spec=[InputSpec([2, 4], "float32", "x")])
    return path, layer


def test_capi_bridge_roundtrip(exported_model):
    """The Python half of the C API, via the exact calls capi.cc makes."""
    path, layer = exported_model
    from paddle_tpu.inference import capi_bridge as bridge
    h = bridge.new_predictor(path, "")
    try:
        assert bridge.input_names(h)
        x = (np.arange(8, dtype=np.float32) * 0.5 - 2.0).reshape(2, 4)
        bridge.set_input(h, bridge.input_names(h)[0],
                         memoryview(x.tobytes()), [2, 4], 0)
        bridge.run(h)
        raw, shape, code = bridge.get_output(h, bridge.output_names(h)[0])
        got = np.frombuffer(raw, np.float32).reshape(shape)
        want = layer(paddle.to_tensor(x)).numpy()
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        assert code == 0
    finally:
        bridge.delete_predictor(h)


@pytest.mark.skipif(not _build_lib(), reason="libpaddle_capi.so unavailable")
def test_capi_from_c_program(exported_model, tmp_path):
    path, layer = exported_model
    src = tmp_path / "driver.c"
    src.write_text(C_DRIVER)
    exe = str(tmp_path / "driver")
    subprocess.run(
        ["g++", "-x", "c++", str(src), "-o", exe, f"-I{CSRC}",
         f"-L{CSRC}", "-lpaddle_capi", f"-Wl,-rpath,{CSRC}"],
        check=True, capture_output=True, timeout=120)
    # the artifact was exported on cpu, so pin the child to cpu
    env = dict(os.environ, PADDLE_TPU_ROOT=ROOT, JAX_PLATFORMS="cpu")
    proc = subprocess.run([exe, path], capture_output=True, text=True,
                          timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr
    out = json.loads(proc.stdout.strip())
    want = layer(paddle.to_tensor(
        (np.arange(8, dtype=np.float32) * 0.5 - 2.0).reshape(2, 4))).numpy()
    got = np.asarray(out["values"], np.float32).reshape(out["shape"])
    assert out["dtype"] == 0
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
