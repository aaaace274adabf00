"""Layer — the module system.

Reference parity: ``python/paddle/fluid/dygraph/layers.py:76`` (class Layer:
parameters/buffers/sublayers/hooks/state_dict/train-eval) and ParamBase
(``fluid/framework.py:5383``).

TPU-native design: a Layer is simultaneously the eager module AND the
functional-program template: ``paddle_tpu.jit.functional_call`` temporarily
rebinds parameter storage to traced arrays, so the same ``forward`` serves
eager execution, ``jax.jit`` tracing, and sharded pjit training steps.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Callable, Iterator

import numpy as np
import jax
import jax.numpy as jnp

from ...core.tensor import Tensor, Parameter
from ...core import dtype as dtypes
from .. import initializer as I


class LazyGuard:
    """``paddle.LazyGuard``: parameters created inside the guard are
    DECLARED, with their shape and dtype, and get no initial value —
    ``p._data`` is a ``jax.ShapeDtypeStruct`` until ``p.set_value(...)``
    (a checkpoint or seeded weights) or ``p.initialize()`` (the
    initializer the layer asked for) gives one.  A model of billions
    of parameters that is loaded right after construction is built so
    without the float32 initial values it would throw away.

        with nn.LazyGuard():
            net = Net()             # no storage
        net.to(dtype="bfloat16")    # still none
        p.set_value(array)          # now

    ``named_parameters`` / ``state_dict`` work on a declared model
    (names, shapes, dtypes); computing with it does not."""

    _depth = 0

    def __enter__(self):
        LazyGuard._depth += 1
        return self

    def __exit__(self, *exc):
        LazyGuard._depth -= 1


class Layer:
    def __init__(self, name_scope=None, dtype="float32"):
        object.__setattr__(self, "_parameters", OrderedDict())
        object.__setattr__(self, "_buffers", OrderedDict())
        object.__setattr__(self, "_sub_layers", OrderedDict())
        self.training = True
        self._dtype = dtype
        self._forward_pre_hooks = OrderedDict()
        self._forward_post_hooks = OrderedDict()
        self._name_scope = name_scope or self.__class__.__name__.lower()

    # -- construction -----------------------------------------------------
    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None):
        from ..param_attr import ParamAttr
        dtype = dtype or self._dtype
        attr = ParamAttr._to_attr(attr)
        if attr is False:
            return None
        # priority mirrors the reference: ParamAttr.initializer >
        # set_global_initializer > the layer's default
        init = (attr.initializer if attr and attr.initializer is not None
                else None)
        if init is None:
            init = I.global_initializer(is_bias)
        if init is None:
            init = default_initializer
        if init is None:
            init = I.Constant(0.0) if is_bias else I.XavierNormal()
        lazy = LazyGuard._depth > 0
        data = (jax.ShapeDtypeStruct(tuple(int(n) for n in shape),
                                     dtypes.to_jax(dtype))
                if lazy else init(shape, dtype))
        p = Parameter(data, dtype=dtype,
                      name=(attr.name if attr else None),
                      trainable=(attr.trainable if attr else True))
        if lazy:
            p._lazy_init = (init, shape, dtype)
        if attr and attr.learning_rate != 1.0:
            p.optimize_attr = {"learning_rate": attr.learning_rate}
        if attr is not None and attr.regularizer is not None:
            p.regularizer = attr.regularizer
        return p

    def create_tensor(self, name=None, dtype=None):
        t = Tensor(jnp.zeros([0], dtypes.to_jax(dtype or self._dtype)))
        if name:
            t.name = name
        return t

    def add_parameter(self, name, parameter):
        if parameter is not None and not isinstance(parameter, Parameter):
            raise TypeError("add_parameter expects Parameter or None")
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name, sublayer):
        self._sub_layers[str(name)] = sublayer
        return sublayer

    def register_buffer(self, name, tensor, persistable=True):
        if tensor is not None and not isinstance(tensor, Tensor):
            tensor = Tensor(tensor)
        if tensor is not None:
            tensor.persistable = persistable
            tensor.stop_gradient = True
        self._buffers[name] = tensor
        return tensor

    # -- attribute protocol ----------------------------------------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__ first")
            params[name] = value
            self.__dict__.pop(name, None)
            self._sub_layers.pop(name, None)
            self._buffers.pop(name, None)
            return
        layers = self.__dict__.get("_sub_layers")
        if isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call Layer.__init__ first")
            layers[name] = value
            self.__dict__.pop(name, None)
            return
        if params is not None and name in params:
            if value is None:
                params.pop(name)
            else:
                raise TypeError(
                    "cannot replace Parameter %r with non-Parameter" % name)
        if layers is not None and name in layers and not isinstance(
                value, Layer):
            layers.pop(name)
        buffers = self.__dict__.get("_buffers")
        if buffers is not None and name in buffers:
            buffers[name] = value if not isinstance(
                value, np.ndarray) else Tensor(value)
            return
        object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(
            f"'{type(self).__name__}' object has no attribute '{name}'")

    def __delattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        extra = (list(self._parameters) + list(self._buffers)
                 + list(self._sub_layers))
        return sorted(set(super().__dir__() + extra))

    # -- iteration --------------------------------------------------------
    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters(
            include_sublayers=include_sublayers)]

    def named_parameters(self, prefix="", include_sublayers=True,
                         include_self=True):
        seen = set()
        for name, layer in self.named_sublayers(
                prefix=prefix, include_self=True):
            if not include_sublayers and layer is not self:
                continue
            for pname, p in layer._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                yield (name + "." + pname if name else pname), p

    def named_sublayers(self, prefix="", include_self=False, layers_set=None
                        ) -> Iterator[tuple[str, "Layer"]]:
        layers_set = layers_set if layers_set is not None else set()
        if id(self) in layers_set:
            return
        layers_set.add(id(self))
        if include_self:
            yield prefix, self
        for name, layer in self._sub_layers.items():
            if layer is None:
                continue
            sub_prefix = prefix + "." + name if prefix else name
            yield from layer.named_sublayers(
                prefix=sub_prefix, include_self=True, layers_set=layers_set)

    def sublayers(self, include_self=False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def children(self):
        return iter(self._sub_layers.values())

    def named_children(self):
        return iter(self._sub_layers.items())

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(
            include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        seen = set()
        for name, layer in self.named_sublayers(include_self=True,
                                                prefix=prefix):
            if not include_sublayers and layer is not self:
                continue
            for bname, b in layer._buffers.items():
                if b is None or id(b) in seen:
                    continue
                seen.add(id(b))
                yield (name + "." + bname if name else bname), b

    # -- state ------------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix="", use_hook=True):
        dest = destination if destination is not None else OrderedDict()
        for name, p in self.named_parameters(
                prefix=structured_name_prefix,
                include_sublayers=include_sublayers):
            dest[name] = p
        for name, b in self.named_buffers(
                prefix=structured_name_prefix,
                include_sublayers=include_sublayers):
            if b is not None and b.persistable:
                dest[name] = b
        # note: values are live Tensors (paddle semantics), not copies
        return dest

    def set_state_dict(self, state_dict, use_structured_name=True):
        missing, unexpected = [], list(state_dict.keys())
        own = self.state_dict()
        for name, target in own.items():
            if name in state_dict:
                value = state_dict[name]
                arr = value.numpy() if isinstance(value, Tensor) else \
                    np.asarray(value)
                if list(arr.shape) != target.shape:
                    raise ValueError(
                        f"shape mismatch for {name}: checkpoint "
                        f"{list(arr.shape)} vs layer {target.shape}")
                target.set_value(arr.astype(target.numpy().dtype))
                unexpected.remove(name)
            else:
                missing.append(name)
        return missing, unexpected

    load_dict = set_state_dict
    set_dict = set_state_dict

    # -- mode / utils -----------------------------------------------------
    def train(self):
        self.training = True
        for layer in self.sublayers():
            layer.training = True
        return self

    def eval(self):
        self.training = False
        for layer in self.sublayers():
            layer.training = False
        return self

    def apply(self, fn: Callable):
        for layer in self.sublayers(include_self=True):
            fn(layer)
        return self

    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            jdt = dtypes.to_jax(dtype)
            for p in self.parameters():
                if not jnp.issubdtype(p._data.dtype, jnp.floating):
                    continue
                if isinstance(p._data, jax.ShapeDtypeStruct):
                    # declared under LazyGuard: nothing to cast
                    p._data = jax.ShapeDtypeStruct(p._data.shape, jdt)
                else:
                    p._data = p._data.astype(jdt)
            for b in self.buffers():
                if b is not None and jnp.issubdtype(b._data.dtype,
                                                    jnp.floating):
                    b._data = b._data.astype(jdt)
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    # -- hooks ------------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        handle = HookRemoveHelper(self._forward_pre_hooks)
        self._forward_pre_hooks[handle.id] = hook
        return handle

    def register_forward_post_hook(self, hook):
        handle = HookRemoveHelper(self._forward_post_hooks)
        self._forward_post_hooks[handle.id] = hook
        return handle

    # -- call -------------------------------------------------------------
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        for hook in self._forward_pre_hooks.values():
            result = hook(self, inputs)
            if result is not None:
                inputs = result if isinstance(result, tuple) else (result,)
        out = self.forward(*inputs, **kwargs)
        for hook in self._forward_post_hooks.values():
            result = hook(self, inputs, out)
            if result is not None:
                out = result
        return out

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, layer in self._sub_layers.items():
            rep = repr(layer).split("\n")
            rep = [rep[0]] + ["  " + r for r in rep[1:]]
            lines.append(f"  ({name}): " + "\n".join(rep))
        main = self.__class__.__name__ + "(" + extra
        if lines:
            return main + "\n" + "\n".join(lines) + "\n)"
        return main + ")"

    def full_name(self):
        return self._name_scope


class HookRemoveHelper:
    _next_id = [0]

    def __init__(self, hooks):
        HookRemoveHelper._next_id[0] += 1
        self.id = HookRemoveHelper._next_id[0]
        self._hooks = hooks

    def remove(self):
        self._hooks.pop(self.id, None)


class LayerList(Layer):
    """paddle.nn.LayerList (reference: fluid/dygraph/container.py)."""

    def __init__(self, sublayers=None):
        super().__init__()
        if sublayers is not None:
            for i, l in enumerate(sublayers):
                self.add_sublayer(str(i), l)

    def __len__(self):
        return len(self._sub_layers)

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return LayerList(list(self._sub_layers.values())[idx])
        return self._sub_layers[str(idx % len(self) if idx < 0 else idx)]

    def __setitem__(self, idx, layer):
        self._sub_layers[str(idx)] = layer

    def __iter__(self):
        return iter(self._sub_layers.values())

    def append(self, layer):
        self.add_sublayer(str(len(self)), layer)
        return self

    def insert(self, index, layer):
        layers = list(self._sub_layers.values())
        layers.insert(index, layer)
        self._sub_layers.clear()
        for i, l in enumerate(layers):
            self._sub_layers[str(i)] = l

    def extend(self, layers):
        for l in layers:
            self.append(l)
        return self


class Sequential(Layer):
    """paddle.nn.Sequential"""

    def __init__(self, *layers):
        super().__init__()
        if len(layers) == 1 and isinstance(layers[0], OrderedDict):
            for name, layer in layers[0].items():
                self.add_sublayer(name, layer)
        elif layers and isinstance(layers[0], (list, tuple)) and not isinstance(
                layers[0], Layer):
            for name, layer in layers:
                self.add_sublayer(name, layer)
        else:
            for i, layer in enumerate(layers):
                self.add_sublayer(str(i), layer)

    def __getitem__(self, idx):
        return list(self._sub_layers.values())[idx]

    def __len__(self):
        return len(self._sub_layers)

    def forward(self, x):
        for layer in self._sub_layers.values():
            x = layer(x)
        return x


class ParameterList(Layer):
    def __init__(self, parameters=None):
        super().__init__()
        if parameters is not None:
            for i, p in enumerate(parameters):
                self.add_parameter(str(i), p)

    def __len__(self):
        return len(self._parameters)

    def __getitem__(self, idx):
        return self._parameters[str(idx)]

    def __iter__(self):
        return iter(self._parameters.values())

    def append(self, parameter):
        self.add_parameter(str(len(self)), parameter)
        return self
