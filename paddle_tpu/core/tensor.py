"""Eager Tensor.

Reference parity: dygraph ``VarBase`` (``paddle/fluid/imperative/layer.h``,
pybind surface ``pybind/imperative.cc``) + ``framework::Tensor``
(``paddle/fluid/framework/tensor.h:89``).

TPU-native design: a thin mutable handle around an immutable ``jax.Array``.
There is no allocator / Place zoo — XLA owns HBM; "mutation" (set_value,
optimizer updates, in-place ops) swaps the underlying array.  The same Tensor
object flows through eager ops and through jit traces (where ``_data`` is a
tracer), which is what lets one Layer codebase serve both execution modes
(the reference needed two runtimes for this — imperative/ + framework/).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from . import dtype as dtypes
from . import device as device_mod
from . import autograd

Value = object  # jax.Array | tracer


class Tensor:
    _next_id = [0]

    __slots__ = ("_data", "_stop_gradient", "grad", "_grad_node",
                 "_retain_grad", "name", "persistable", "__weakref__",
                 "__dict__")

    def __init__(self, data, dtype=None, place=None, stop_gradient=True,
                 name=None):
        if isinstance(data, Tensor):
            data = data._data
        if isinstance(data, jax.ShapeDtypeStruct):
            # a declared value (nn.LazyGuard): shape and dtype, no
            # storage until set_value() / Parameter.initialize()
            if dtype is not None:
                data = jax.ShapeDtypeStruct(data.shape,
                                            dtypes.to_jax(dtype))
        elif not isinstance(data, jax.Array) and not isinstance(
                data, jax.core.Tracer):
            data = np.asarray(data)
            if dtype is None and data.dtype == np.float64:
                # numpy literals default to f64; paddle defaults to f32
                data = data.astype(dtypes.to_jax(dtypes.get_default_dtype()))
            dev = device_mod.jax_device(place)
            data = jnp.asarray(
                data, dtypes.to_jax(dtype) if dtype else None)
            if isinstance(data, jax.Array):
                data = jax.device_put(data, dev)
        elif dtype is not None:
            data = data.astype(dtypes.to_jax(dtype))
        self._data = data
        self._stop_gradient = stop_gradient
        self.grad = None
        self._grad_node = None
        self._retain_grad = False
        Tensor._next_id[0] += 1
        self.name = name or f"tensor_{Tensor._next_id[0]}"
        self.persistable = False

    # -- metadata ---------------------------------------------------------
    # paddle semantics: `trainable` is the inverse alias of `stop_gradient`
    # (fluid Parameter keeps them in sync); one backing slot avoids the two
    # flags drifting apart when users flip stop_gradient after construction.
    @property
    def stop_gradient(self):
        return self._stop_gradient

    @stop_gradient.setter
    def stop_gradient(self, value):
        self._stop_gradient = bool(value)

    @property
    def trainable(self):
        return not self._stop_gradient

    @trainable.setter
    def trainable(self, value):
        self._stop_gradient = not value

    @property
    def data(self):
        return self

    @property
    def shape(self):
        return list(self._data.shape)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def size(self):
        return int(np.prod(self._data.shape)) if self._data.shape else 1

    @property
    def dtype(self):
        return dtypes.canonical_name(self._data.dtype)

    @property
    def place(self):
        return device_mod.current_place()

    @property
    def is_leaf(self):
        return self._grad_node is None

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._data.shape[0]

    # -- value access -----------------------------------------------------
    def numpy(self):
        return np.asarray(self._data)

    def item(self, *args):
        if args:
            return self.numpy().item(*args)
        return self.numpy().item()

    def tolist(self):
        return self.numpy().tolist()

    def __array__(self, dtype=None):
        arr = self.numpy()
        return arr.astype(dtype) if dtype else arr

    def __float__(self):
        return float(self.item())

    def __int__(self):
        return int(self.item())

    def __bool__(self):
        return bool(self.item())

    def __index__(self):
        return int(self.item())

    # -- autograd ---------------------------------------------------------
    def backward(self, grad_tensor=None, retain_graph=False,
                 create_graph=False):
        autograd.backward([self], [grad_tensor], retain_graph=retain_graph,
                          create_graph=create_graph)

    def retain_grads(self):
        self._retain_grad = True

    def clear_grad(self):
        self.grad = None

    def clear_gradient(self):
        self.grad = None

    def detach(self):
        t = Tensor(self._data, stop_gradient=True)
        return t

    def detach_(self):
        self._grad_node = None
        self.stop_gradient = True
        return self

    # -- mutation facade --------------------------------------------------
    def set_value(self, value):
        if isinstance(value, Tensor):
            value = value._data
        value = jnp.asarray(value, self._data.dtype)
        if tuple(value.shape) != tuple(self._data.shape):
            raise ValueError(
                "set_value shape mismatch: %s vs %s"
                % (value.shape, self._data.shape))
        self._data = value
        return self

    def copy_(self, other):
        return self.set_value(other)

    def zero_(self):
        self._data = jnp.zeros_like(self._data)
        return self

    def fill_(self, v):
        self._data = jnp.full_like(self._data, v)
        return self

    # -- conversions ------------------------------------------------------
    def astype(self, dt):
        from .. import ops
        return ops.cast(self, dt)

    def cast(self, dt):
        return self.astype(dt)

    def clone(self):
        from .. import ops
        return ops.assign(self)

    def cpu(self):
        return self

    def cuda(self, device_id=None, blocking=True):
        # reference VarBase.cuda; placement is XLA's job on this backend
        return self

    def to(self, *a, **k):
        return self

    def pin_memory(self):
        return self

    def value(self):
        # reference VarBase.value() returns the underlying Variable; the
        # Tensor IS the value holder here
        return self

    def gradient(self):
        """reference varbase_patch_methods gradient() — numpy grad or
        None."""
        return None if self.grad is None else self.grad.numpy()

    def contiguous(self):
        return self

    def __repr__(self):
        grad_info = "" if self.stop_gradient else ", stop_gradient=False"
        import jax as _jax
        if isinstance(self._data, _jax.core.Tracer):
            # under jit there is no concrete value to show — raising
            # from repr would turn every print/log of a traced tensor
            # into a TracerArrayConversionError (use @to_static's print
            # conversion to see runtime values)
            return (f"Tensor(shape={self.shape}, dtype={self.dtype}"
                    f"{grad_info}, <traced>)")
        return (f"Tensor(shape={self.shape}, dtype={self.dtype}{grad_info},\n"
                f"       {np.array2string(self.numpy(), threshold=40)})")

    __str__ = __repr__

    def __hash__(self):
        return id(self)

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # Arithmetic / indexing operators are attached by paddle_tpu.ops at
    # import time (see ops/__init__.py) to avoid an import cycle.


class Parameter(Tensor):
    """Trainable tensor (reference: fluid ParamBase, framework.py:5383)."""

    def __init__(self, data, dtype=None, name=None, trainable=True):
        super().__init__(data, dtype=dtype, stop_gradient=not trainable,
                         name=name)
        self.persistable = True
        self._lazy_init = None  # (initializer, shape, dtype) while the
        #   value is only declared (nn.LazyGuard)

    @property
    def is_declared(self):
        """True while the parameter has a shape and a dtype but no
        value (created under ``nn.LazyGuard``)."""
        return isinstance(self._data, jax.ShapeDtypeStruct)

    def initialize(self):
        """Give a declared parameter the value its initializer would
        have given it at creation (``paddle.LazyGuard``'s
        ``param.initialize()``); a no-op on a parameter that has a
        value."""
        if self.is_declared:
            init, shape, dtype = self._lazy_init
            self._data = Tensor(init(shape, dtype))._data.astype(
                self._data.dtype)
        self._lazy_init = None
        return self

    def __repr__(self):
        if self.is_declared:
            return (f"Parameter declared: shape {list(self._data.shape)}"
                    f" dtype {self._data.dtype}")
        return "Parameter containing:\n" + super().__repr__()


def to_tensor(data, dtype=None, place=None, stop_gradient=True):
    """paddle.to_tensor"""
    if isinstance(data, Tensor):
        if dtype is not None and data.dtype != dtypes.canonical_name(dtype):
            data = data.astype(dtype)
        t = Tensor(data._data, stop_gradient=stop_gradient)
        return t
    return Tensor(data, dtype=dtype, place=place, stop_gradient=stop_gradient)
