"""paddle_tpu — a TPU-native deep learning framework with the capabilities
of PaddlePaddle (~v2.0), built on JAX/XLA/pjit/Pallas.

Blueprint: /root/repo/SURVEY.md (structural analysis of the reference).
The public API mirrors ``python/paddle`` where that API is device-neutral;
everything CUDA-shaped in the reference (streams, places, NCCL rings, kernel
registries) is replaced by XLA compilation over device meshes.
"""
from __future__ import annotations

__version__ = "2.0.0-tpu"  # tracks the reference's 2.0 API surface

# -- core ----------------------------------------------------------------
from .core.tensor import Tensor, Parameter, to_tensor  # noqa: F401
from .core.autograd import no_grad, enable_grad  # noqa: F401
from .core import autograd as _autograd
from .core.device import (  # noqa: F401
    set_device, get_device, device_count, CPUPlace, TPUPlace,
    is_compiled_with_cuda, is_compiled_with_xpu, is_compiled_with_tpu,
)
from .core.dtype import (  # noqa: F401
    set_default_dtype, get_default_dtype,
    bool_ as bool8, uint8, int8, int16, int32, int64,
    float16, bfloat16, float32, float64, complex64, complex128,
)
from .core.flags import set_flags, get_flags  # noqa: F401
from .core.rng import seed  # noqa: F401
from .core import rng as _rng

# -- ops (also attaches Tensor methods) ----------------------------------
from .ops import *  # noqa: F401,F403
from .ops import linalg  # noqa: F401
from . import ops  # noqa: F401


def grad(outputs, inputs, grad_outputs=None, retain_graph=None,
         create_graph=False, only_inputs=True, allow_unused=False):
    """paddle.grad — gradients of outputs wrt inputs via the eager tape.

    Implemented by running backward with retain_graph and reading the leaf
    grads.  ``create_graph=True`` records the backward itself on the tape
    (reference: imperative/partial_grad_engine.cc), so the returned grads
    are differentiable — gradient-penalty / double-grad training works.
    """
    outputs = outputs if isinstance(outputs, (list, tuple)) else [outputs]
    inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
    if retain_graph is None:
        retain_graph = create_graph
    saved = [(t.grad, t._retain_grad) for t in inputs]
    for t in inputs:
        t.grad = None
        t._retain_grad = True
    _autograd.backward(list(outputs), grad_outputs,
                       retain_graph=bool(retain_graph),
                       create_graph=create_graph,
                       _leaf_targets={id(t) for t in inputs})
    grads = []
    for t, (old, old_retain) in zip(inputs, saved):
        g = t.grad
        if g is None and not allow_unused:
            g = ops.zeros_like(t)
        grads.append(g)
        t.grad = old
        t._retain_grad = old_retain
    return grads


# -- subsystems ----------------------------------------------------------
from . import nn  # noqa: E402,F401
from . import models  # noqa: E402,F401
from . import optimizer  # noqa: E402,F401
from . import amp  # noqa: E402,F401
from . import io  # noqa: E402,F401
from . import jit  # noqa: E402,F401
from . import distributed  # noqa: E402,F401
from . import metric  # noqa: E402,F401
from . import vision  # noqa: E402,F401
from . import hapi  # noqa: E402,F401
from . import static  # noqa: E402,F401
from . import distribution  # noqa: E402,F401
from . import text  # noqa: E402,F401
from . import inference  # noqa: E402,F401
from . import utils  # noqa: E402,F401
from . import monitor  # noqa: E402,F401
from . import serving  # noqa: E402,F401
from .framework.io import save, load  # noqa: E402,F401
from .static import (enable_static, disable_static,  # noqa: E402,F401
                     in_dynamic_mode)
from .ops.manipulation import flip as reverse  # noqa: E402,F401
from .static.program import in_static_mode  # noqa: E402,F401

# ---- 1.x-compat aliases & auxiliary modules (reference __init__.py
# DEFINE_ALIAS block + module imports) ------------------------------------
from .ops.compat_ops import (  # noqa: E402,F401
    add_n, kron, broadcast_shape, rank, shape, is_tensor, is_empty,
    unstack, slice, strided_slice, crop_tensor, crop_tensor as crop,
    fill_constant,
    create_global_var, create_parameter, has_inf, has_nan,
    elementwise_add, elementwise_sub, elementwise_mul, elementwise_div,
    elementwise_pow, elementwise_mod, elementwise_floordiv,
    elementwise_max, elementwise_min,
    reduce_sum, reduce_mean, reduce_max, reduce_min, reduce_prod,
    tanh_, squeeze_, unsqueeze_, scatter_, exp_, sqrt_, ceil_, floor_,
    round_, clip_, subtract_, add_, set_printoptions,
    create_array, array_write, array_read, array_length)
from .ops.linalg import (cholesky, cross, dist, histogram,  # noqa: E402,F401
                         inverse, norm, bincount)
from . import device  # noqa: E402,F401
from . import regularizer  # noqa: E402,F401
from . import compat  # noqa: E402,F401
from . import sysconfig  # noqa: E402,F401
from . import onnx  # noqa: E402,F401
from . import incubate  # noqa: E402,F401
from . import version  # noqa: E402,F401
from .batch import batch  # noqa: E402,F401
from . import reader  # noqa: E402,F401
from .nn.param_attr import ParamAttr  # noqa: E402,F401
from .core.tensor import Tensor as VarBase  # noqa: E402,F401
from .core.tensor import Tensor as LoDTensor  # noqa: E402,F401
from .hapi import callbacks  # noqa: E402,F401
from . import ops as tensor  # noqa: E402,F401  (paddle.tensor alias)
from .static import data  # noqa: E402,F401

LoDTensorArray = list  # reference: vector<LoDTensor> bound to a list

full_version = __version__
commit = "tpu-native"


def get_tensor_from_selected_rows(x, name=None):
    """Densify a SelectedRows gradient (reference:
    get_tensor_from_selected_rows_op.cc).  Eager ``nn.Embedding(...,
    sparse=True)`` grads are ``core.selected_rows.SelectedRows``; this
    returns their scatter-added dense form.  Dense tensors pass through."""
    from .core.selected_rows import SelectedRows
    if isinstance(x, SelectedRows):
        return Tensor(x._data, stop_gradient=True)
    return x


def in_dygraph_mode():
    return in_dynamic_mode()


def enable_dygraph(place=None):
    disable_static()


def disable_dygraph():
    enable_static()


class CUDAPlace:
    """Accepted for API compat; placement is XLA's job on TPU."""

    def __init__(self, dev_id=0):
        self.dev_id = dev_id


class CUDAPinnedPlace:
    pass


class XPUPlace:
    def __init__(self, dev_id=0):
        self.dev_id = dev_id


def get_cudnn_version():
    return None  # no cuDNN on TPU


def get_cuda_rng_state():
    from .core import rng as _rng
    return [_rng.get_seed()]


def set_cuda_rng_state(state):
    from .core import rng as _rng
    if state:
        _rng.seed(int(state[0]))


def monkey_patch_variable():
    pass  # operators are attached at import time (ops/__init__.py)


def monkey_patch_math_varbase():
    pass


def summary(net, input_size=None, dtypes=None):
    """paddle.summary (reference: hapi/model_summary.py)."""
    from .hapi.model import Model
    return Model(net).summary(input_size, dtype=dtypes)


def flops(net, input_size, custom_ops=None, print_detail=False):
    """Rough FLOPs count: 2*params per MAC-dominated layer (reference:
    hapi/dynamic_flops.py walks per-layer hooks; here dense/conv params
    dominate on the MXU)."""
    import numpy as _np
    total = 0
    for _, p in net.named_parameters():
        n = int(_np.prod(p.shape))
        if len(p.shape) >= 2:
            total += 2 * n
    return total
from .hapi.model import Model  # noqa: E402,F401
from .nn.layer.base import Layer, LazyGuard  # noqa: E402,F401
from . import framework  # noqa: E402,F401
from .framework import random  # noqa: E402,F401

DataParallel = None  # set by paddle_tpu.distributed at import


def _late_bind():
    global DataParallel
    from .distributed.parallel import DataParallel as _DP
    DataParallel = _DP


_late_bind()
del _late_bind


# fluid namespace last: it re-exports names defined above (places, etc.)
from . import fluid  # noqa: E402,F401
from . import dataset  # noqa: E402,F401  (1.x reader factories)
from . import quantization  # noqa: E402,F401
