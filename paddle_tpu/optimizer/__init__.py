"""Optimizers.

Reference parity: ``python/paddle/optimizer/optimizer.py`` (base) and the
per-op kernels in ``paddle/fluid/operators/optimizers/`` (sgd_op, momentum_op,
adam_op, adamw, lamb_op, lars_momentum_op, adagrad, rmsprop, adadelta).

TPU-native design: each optimizer is a **pure functional update rule**
``_update(param, grad, state, lr, ...) -> (new_param, new_state)`` over jax
arrays.  The eager facade (``step()``) applies it per-parameter; the jit path
(hapi / fleet train steps) applies the SAME rule over whole pytrees inside a
compiled step — one fused XLA kernel for the entire update, which is what the
reference's fuse_optimizer_ops_pass approximated by hand.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..core import autograd
from . import lr  # noqa: F401
from .lr import LRScheduler


class Optimizer:
    #: update rule is strictly per-element (safe to fuse across params);
    #: LAMB/LARS-style per-PARAM trust ratios must keep this False
    _elementwise_rule = False
    def __init__(self, learning_rate=0.001, parameters=None,
                 weight_decay=None, grad_clip=None, name=None):
        if parameters is not None:
            parameters = list(parameters)
        self._parameter_list = parameters
        self._learning_rate = learning_rate
        if grad_clip is None:
            # 1.x fluid.clip.set_gradient_clip registers a process-wide
            # default consumed by optimizers built without an explicit
            # grad_clip (reference: fluid/clip.py set_gradient_clip)
            from ..nn import clip as _clip_mod
            grad_clip = _clip_mod.get_gradient_clip()
        self._grad_clip = grad_clip
        self._weight_decay = self._parse_wd(weight_decay)
        self._accumulators: dict[int, dict] = {}
        self._step_count = 0
        # opt-in flat-slab fused update (see _fused_flat_update);
        # PADDLE_TPU_FUSE_OPT=1 enables globally, or set
        # opt.fuse_update = True per instance
        import os as _os
        self.fuse_update = _os.environ.get(
            "PADDLE_TPU_FUSE_OPT", "0") == "1"

    @staticmethod
    def _parse_wd(weight_decay):
        if weight_decay is None:
            return 0.0
        if isinstance(weight_decay, (int, float)):
            return float(weight_decay)
        # regularizer object (L2Decay) with a coeff attribute
        return float(getattr(weight_decay, "_coeff",
                             getattr(weight_decay, "coeff", 0.0)))

    # -- lr ---------------------------------------------------------------
    def get_lr(self):
        if isinstance(self._learning_rate, LRScheduler):
            return self._learning_rate()
        return float(self._learning_rate)

    def set_lr(self, value):
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError("cannot set_lr when using an LRScheduler")
        self._learning_rate = float(value)

    def _get_param_lr(self, p):
        mult = 1.0
        attr = getattr(p, "optimize_attr", None)
        if attr:
            mult = attr.get("learning_rate", 1.0)
        return self.get_lr() * mult

    # -- functional core (overridden per optimizer) -----------------------
    def _init_state(self, param):
        """-> dict of state arrays for one param."""
        return {}

    def _update(self, param, grad, state, lr):
        """pure: (param, grad, state dicts of arrays, lr) ->
        (new_param, new_state)."""
        raise NotImplementedError

    def _update_sparse(self, param, rows, vals, state, lr):
        """Sparse (SelectedRows) update: `rows` are unique indices into
        dim 0 of `param`, `vals` the merged per-row gradients (reference:
        sparse kernels in operators/optimizers/, e.g. adam_op.h
        SparseAdamFunctor).  Base fallback densifies — correct for every
        rule; SGD/Momentum/Adam override with row-wise math."""
        g = jnp.zeros(param.shape, vals.dtype).at[rows].add(vals)
        return self._update(param, g, state, lr)

    # -- pytree API for jit'd train steps ---------------------------------
    def init_state_tree(self, params_tree):
        return jax.tree_util.tree_map(
            lambda p: self._init_state(p), params_tree,
            is_leaf=lambda x: isinstance(x, jnp.ndarray) or hasattr(
                x, "shape"))

    def apply_gradients_tree(self, params_tree, grads_tree, state_tree,
                             lr, fuse=None):
        """Pure whole-tree update; call inside jit.  ``fuse`` overrides
        ``self.fuse_update`` for this call — TrainStep passes False when
        params are sharded (the flat-slab concat would all-gather
        TP/FSDP/pp shards) without mutating the caller's optimizer."""
        if self._grad_clip is not None:
            grads_tree = self._grad_clip.apply_tree(grads_tree)
        flat_kp, treedef = jax.tree_util.tree_flatten_with_path(params_tree)
        names = ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                          for k in path) for path, _ in flat_kp]
        flat_p = [p for _, p in flat_kp]
        flat_g = treedef.flatten_up_to(grads_tree)
        flat_s = treedef.flatten_up_to(state_tree)
        has_mask = hasattr(self, "_decay_for_name")
        fuse = self.fuse_update if fuse is None else fuse
        # fused path requires all-dense grads: a None grad this call
        # would leave that param's SCALAR state (beta pows) lagging its
        # future group — sharing the group scalar would then silently
        # mis-correct it (see _fused_flat_update's precondition)
        if fuse and self._elementwise_rule \
                and not any(g is None for g in flat_g):
            new_p, new_s = self._fused_flat_update(
                names, flat_p, flat_g, flat_s, lr, has_mask)
            return (jax.tree_util.tree_unflatten(treedef, new_p),
                    jax.tree_util.tree_unflatten(treedef, new_s))
        new_p, new_s = [], []
        for name, p, g, s in zip(names, flat_p, flat_g, flat_s):
            if g is None:
                new_p.append(p)
                new_s.append(s)
                continue
            if has_mask:
                np_, ns = self._update(p, g, s, lr,
                                       decay_on=self._decay_for_name(name))
            else:
                np_, ns = self._update(p, g, s, lr)
            new_p.append(np_)
            new_s.append(ns)
        return (jax.tree_util.tree_unflatten(treedef, new_p),
                jax.tree_util.tree_unflatten(treedef, new_s))

    def _fused_flat_update(self, names, flat_p, flat_g, flat_s, lr,
                           has_mask):
        """Flat-slab update: concatenate params that share (decay mask,
        dtype, state layout) into one vector and run the elementwise
        update rule ONCE per group instead of once per parameter.  A
        ~150-param transformer becomes 2-3 fused update chains over
        large contiguous vectors — the per-parameter path emits hundreds
        of tiny fusions whose fixed overhead the profiler shows in the
        dominant elementwise bucket (round-3 breakdown, record deleted at bring-up).
        Bitwise-equivalent math: every update rule here is per-element,
        scalar state (beta pows) follows an identical trajectory for
        every group member, and concat/split do not touch values.  Only
        rules marked ``_elementwise_rule`` may fuse (LAMB/LARS use
        per-PARAM trust ratios and must stay per-parameter).

        PRECONDITION: every group member's scalar state is equal — true
        whenever all params have stepped together since init (the
        compiled TrainStep path).  The caller falls back to per-param
        whenever any grad is None, so a lag cannot be INTRODUCED through
        this API; state hand-built with divergent scalars is the
        caller's responsibility."""
        import numpy as _np
        groups = {}
        for i, (name, p, g, s) in enumerate(
                zip(names, flat_p, flat_g, flat_s)):
            if g is None:
                continue
            decay_on = self._decay_for_name(name) if has_mask else True
            skey = tuple(sorted(
                (k, str(v.dtype), int(v.ndim)) for k, v in s.items())) \
                if isinstance(s, dict) else ()
            # grad dtype in the key too: mixed-dtype grads within one
            # group would be silently promoted by jnp.concatenate,
            # diverging from the per-param path's native-dtype math
            groups.setdefault(
                (bool(decay_on), str(p.dtype), str(g.dtype), skey),
                []).append(i)
        new_p, new_s = list(flat_p), list(flat_s)
        for (decay_on, _, _, _), idxs in groups.items():
            # _np.prod(()) == 1.0 (scalars); zero-size params correctly
            # contribute empty slices
            sizes = [int(_np.prod(flat_p[i].shape)) for i in idxs]
            offs = _np.cumsum(sizes)[:-1].tolist()
            fp = jnp.concatenate(
                [flat_p[i].reshape(-1) for i in idxs])
            fg = jnp.concatenate(
                [flat_g[i].reshape(-1) for i in idxs])
            s0 = flat_s[idxs[0]]
            fs = {k: (v if v.ndim == 0 else jnp.concatenate(
                [flat_s[i][k].reshape(-1) for i in idxs]))
                for k, v in s0.items()} if isinstance(s0, dict) else s0
            if has_mask:
                nfp, nfs = self._update(fp, fg, fs, lr,
                                        decay_on=decay_on)
            else:
                nfp, nfs = self._update(fp, fg, fs, lr)
            p_parts = jnp.split(nfp, offs)
            s_parts = {k: (jnp.split(v, offs) if v.ndim else v)
                       for k, v in nfs.items()} \
                if isinstance(nfs, dict) else nfs
            for j, i in enumerate(idxs):
                new_p[i] = p_parts[j].reshape(flat_p[i].shape)
                if isinstance(nfs, dict):
                    new_s[i] = {
                        k: (s_parts[k][j].reshape(flat_s[i][k].shape)
                            if nfs[k].ndim else s_parts[k])
                        for k in nfs}
                else:
                    new_s[i] = nfs
        return new_p, new_s

    # -- eager facade -----------------------------------------------------
    def _params(self):
        if self._parameter_list is None:
            raise ValueError(
                "Optimizer needs `parameters=` in eager (dygraph) mode")
        return self._parameter_list

    def step(self):
        self._step_count += 1
        params = [p for p in self._params() if p.trainable]
        pg = [(p, p.grad) for p in params if p.grad is not None]
        if self._grad_clip is not None:
            pg = self._grad_clip(pg)
        from ..core.selected_rows import SelectedRows
        with autograd.no_grad():
            for p, g in pg:
                if g is None:
                    continue
                key = id(p)
                if key not in self._accumulators:
                    self._accumulators[key] = self._init_state(p)
                state = self._accumulators[key]
                if isinstance(g, SelectedRows):
                    rows, vals = g.merged()
                    new_param, new_state = self._update_sparse(
                        p._data, rows, vals.astype(p._data.dtype), state,
                        self._get_param_lr(p))
                else:
                    new_param, new_state = self._update(
                        p._data, g._data.astype(p._data.dtype), state,
                        self._get_param_lr(p))
                p._data = new_param
                self._accumulators[key] = new_state

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        from ..static import program as sprog
        if isinstance(loss, sprog.Variable):
            # static graph mode (reference: Optimizer.minimize appending
            # grad + optimize ops to the program, fluid/optimizer.py)
            pairs = sprog.append_backward(
                loss,
                parameter_list=parameters or self._parameter_list or None)
            sprog.append_optimize(self, loss, pairs)
            return None, pairs
        params = [p for p in self._params() if p.trainable]
        if builtins_all(p.grad is None for p in params) and \
                loss._grad_node is not None:
            loss.backward()
        self.step()
        return None, [(p, p.grad) for p in params]

    def clear_grad(self, set_to_zero=True):
        for p in self._params():
            p.clear_grad()

    clear_gradients = clear_grad

    # -- state ------------------------------------------------------------
    def state_dict(self):
        out = {"__step__": self._step_count}
        if isinstance(self._learning_rate, LRScheduler):
            out["LR_Scheduler"] = self._learning_rate.state_dict()
        if self._parameter_list is not None:
            for i, p in enumerate(self._parameter_list):
                state = self._accumulators.get(id(p))
                if state:
                    for k, v in state.items():
                        out[f"{p.name}__{k}"] = Tensor(v) if not isinstance(
                            v, Tensor) else v
        return out

    def set_state_dict(self, state):
        self._step_count = state.get("__step__", 0)
        if isinstance(self._learning_rate, LRScheduler) and \
                "LR_Scheduler" in state:
            self._learning_rate.set_state_dict(state["LR_Scheduler"])
        if self._parameter_list is None:
            return
        for p in self._parameter_list:
            st = {}
            prefix = f"{p.name}__"
            for k, v in state.items():
                if isinstance(k, str) and k.startswith(prefix):
                    st[k[len(prefix):]] = (v._data if isinstance(v, Tensor)
                                           else jnp.asarray(v))
            if st:
                self._accumulators[id(p)] = st

    set_dict = set_state_dict


builtins_all = all


class SGD(Optimizer):
    """reference: operators/optimizers/sgd_op.cc"""
    _elementwise_rule = True

    def _update(self, param, grad, state, lr):
        if self._weight_decay:
            grad = grad + self._weight_decay * param
        return param - lr * grad, state

    def _update_sparse(self, param, rows, vals, state, lr):
        # reference: sgd_op.h SelectedRows branch — scatter-subtract the
        # touched rows only.  With weight_decay, decay applies to touched
        # rows (the reference rejects regularizers on sparse params
        # outright; scoping decay to touched rows is the sparse semantic).
        if self._weight_decay:
            vals = vals + self._weight_decay * param[rows]
        return param.at[rows].add(-lr * vals), state


class Momentum(Optimizer):
    """reference: operators/optimizers/momentum_op.cc"""
    _elementwise_rule = True

    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def _init_state(self, param):
        shape = param.shape if hasattr(param, "shape") else ()
        dtype = param._data.dtype if isinstance(param, Tensor) else \
            param.dtype
        return {"velocity": jnp.zeros(shape, dtype)}

    def _update(self, param, grad, state, lr):
        if self._weight_decay:
            grad = grad + self._weight_decay * param
        v = self._momentum * state["velocity"] + grad
        if self._nesterov:
            new_param = param - lr * (grad + self._momentum * v)
        else:
            new_param = param - lr * v
        return new_param, {"velocity": v}

    def _update_sparse(self, param, rows, vals, state, lr):
        # reference: momentum_op.h SparseMomentumFunctor — missing rows
        # carry zero grad, so velocity still decays everywhere; grads and
        # decay land only on the touched rows.  Matches the dense rule
        # exactly when weight_decay == 0.
        if self._weight_decay:
            vals = vals + self._weight_decay * param[rows]
        v = self._momentum * state["velocity"]
        v = v.at[rows].add(vals)
        if self._nesterov:
            new_param = param - lr * self._momentum * v
            new_param = new_param.at[rows].add(-lr * vals)
        else:
            new_param = param - lr * v
        return new_param, {"velocity": v}


class Adam(Optimizer):
    """reference: operators/optimizers/adam_op.cc (with bias correction)."""
    _elementwise_rule = True

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=True,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon
        self._lazy = bool(lazy_mode)
        self._multi_precision = bool(multi_precision)

    def _init_state(self, param):
        shape = param.shape if hasattr(param, "shape") else ()
        dtype = param._data.dtype if isinstance(param, Tensor) else \
            param.dtype
        # multi_precision (default, reference: adam_op MasterParam): f32
        # moments for low-precision params.  multi_precision=False keeps
        # moments in the PARAM dtype — halves optimizer-state HBM for
        # bf16 models (2 x 2 bytes/param instead of 2 x 4), the knob the
        # single-chip GPT-3 1.3B fit relies on
        if dtype in (jnp.bfloat16, jnp.float16):
            mdtype = jnp.float32 if self._multi_precision else dtype
        else:
            mdtype = dtype
        return {"moment1": jnp.zeros(shape, mdtype),
                "moment2": jnp.zeros(shape, mdtype),
                "beta1_pow": jnp.ones([], jnp.float32),
                "beta2_pow": jnp.ones([], jnp.float32)}

    def _update_sparse(self, param, rows, vals, state, lr):
        """reference: adam_op.h SparseAdamFunctor.  lazy_mode=True (the
        flag the dense path ignores) updates moments and param ONLY at the
        touched rows — O(batch) work, the embedding-table fast path.
        lazy_mode=False reproduces the dense rule exactly: missing rows
        see zero grad, so their moments decay and bias-corrected updates
        still move them."""
        b1, b2, eps = self._beta1, self._beta2, self._epsilon
        mdtype = state["moment1"].dtype
        g = vals.astype(mdtype)
        b1p = state["beta1_pow"] * b1
        b2p = state["beta2_pow"] * b2
        decay = self._weight_decay if isinstance(self, AdamW) else 0.0
        if not isinstance(self, AdamW) and self._weight_decay:
            # L2-reg folds into the gradient; sparse semantic scopes it
            # to touched rows (see SGD._update_sparse note)
            g = g + self._weight_decay * param[rows].astype(mdtype)
        if self._lazy:
            m_r = b1 * state["moment1"][rows] + (1 - b1) * g
            v_r = b2 * state["moment2"][rows] + (1 - b2) * jnp.square(g)
            update = (m_r / (1 - b1p)) / (jnp.sqrt(v_r / (1 - b2p)) + eps)
            p_r = param[rows].astype(update.dtype)
            if decay and self._decay_allows_rows(param):
                update = update + decay * p_r
            new_param = param.at[rows].set(
                (p_r - lr * update).astype(param.dtype))
            m = state["moment1"].at[rows].set(m_r)
            v = state["moment2"].at[rows].set(v_r)
        else:
            m = b1 * state["moment1"]
            m = m.at[rows].add((1 - b1) * g)
            v = b2 * state["moment2"]
            v = v.at[rows].add((1 - b2) * jnp.square(g))
            update = (m / (1 - b1p)) / (jnp.sqrt(v / (1 - b2p)) + eps)
            if decay and self._decay_allows_rows(param):
                update = update + decay * param.astype(update.dtype)
            new_param = (param.astype(update.dtype) - lr * update).astype(
                param.dtype)
        return new_param, {"moment1": m, "moment2": v, "beta1_pow": b1p,
                           "beta2_pow": b2p}

    def _decay_allows_rows(self, param):
        fn = getattr(self, "_apply_decay_fn", None)
        return fn is None or fn(param)

    def _update(self, param, grad, state, lr, decay_on=True):
        b1, b2, eps = self._beta1, self._beta2, self._epsilon
        g = grad.astype(state["moment1"].dtype)
        if self._weight_decay and not isinstance(self, AdamW):
            g = g + self._weight_decay * param.astype(g.dtype)
        m = b1 * state["moment1"] + (1 - b1) * g
        v = b2 * state["moment2"] + (1 - b2) * jnp.square(g)
        b1p = state["beta1_pow"] * b1
        b2p = state["beta2_pow"] * b2
        m_hat = m / (1 - b1p)
        v_hat = v / (1 - b2p)
        update = m_hat / (jnp.sqrt(v_hat) + eps)
        if isinstance(self, AdamW) and self._weight_decay and decay_on:
            if self._apply_decay_fn is None or self._apply_decay_fn(param):
                update = update + self._weight_decay * param.astype(
                    update.dtype)
        new_param = (param.astype(update.dtype) - lr * update).astype(
            param.dtype)
        return new_param, {"moment1": m, "moment2": v, "beta1_pow": b1p,
                           "beta2_pow": b2p}


class AdamW(Adam):
    """reference: operators/optimizers/adamw (decoupled decay)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01,
                 lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=True, name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         weight_decay, grad_clip, lazy_mode, multi_precision,
                         name)
        # paddle passes the param NAME to the predicate
        self._decay_param_fun = apply_decay_param_fun
        self._apply_decay_fn = None

    def _decay_for_name(self, name):
        """Used by the jit/tree path; `name` is the pytree path (the
        train-step builder keys params by their layer-qualified name)."""
        if self._decay_param_fun is None:
            return True
        return bool(self._decay_param_fun(name))

    def step(self):
        # resolve name-based decay predicate into per-step closure
        if self._decay_param_fun is not None:
            fn = self._decay_param_fun
            names = {id(p._data): fn(p.name) for p in self._params()}

            def pred(param):
                return names.get(id(param), True)
            self._apply_decay_fn = pred
        super().step()
        self._apply_decay_fn = None


class Adamax(Optimizer):
    _elementwise_rule = True
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _init_state(self, param):
        shape = param.shape if hasattr(param, "shape") else ()
        dtype = param._data.dtype if isinstance(param, Tensor) else \
            param.dtype
        return {"moment": jnp.zeros(shape, dtype),
                "inf_norm": jnp.zeros(shape, dtype),
                "beta1_pow": jnp.ones([], jnp.float32)}

    def _update(self, param, grad, state, lr):
        b1, b2, eps = self._beta1, self._beta2, self._epsilon
        if self._weight_decay:
            grad = grad + self._weight_decay * param
        m = b1 * state["moment"] + (1 - b1) * grad
        u = jnp.maximum(b2 * state["inf_norm"], jnp.abs(grad))
        b1p = state["beta1_pow"] * b1
        new_param = param - lr / (1 - b1p) * m / (u + eps)
        return new_param.astype(param.dtype), {
            "moment": m, "inf_norm": u, "beta1_pow": b1p}


class Adagrad(Optimizer):
    _elementwise_rule = True
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None,
                 initial_accumulator_value=0.0, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._epsilon = epsilon
        self._init_value = initial_accumulator_value

    def _init_state(self, param):
        shape = param.shape if hasattr(param, "shape") else ()
        dtype = param._data.dtype if isinstance(param, Tensor) else \
            param.dtype
        return {"moment": jnp.full(shape, self._init_value, dtype)}

    def _update(self, param, grad, state, lr):
        if self._weight_decay:
            grad = grad + self._weight_decay * param
        m = state["moment"] + jnp.square(grad)
        new_param = param - lr * grad / (jnp.sqrt(m) + self._epsilon)
        return new_param, {"moment": m}


class RMSProp(Optimizer):
    _elementwise_rule = True
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _init_state(self, param):
        shape = param.shape if hasattr(param, "shape") else ()
        dtype = param._data.dtype if isinstance(param, Tensor) else \
            param.dtype
        return {"mean_square": jnp.zeros(shape, dtype),
                "mean_grad": jnp.zeros(shape, dtype),
                "velocity": jnp.zeros(shape, dtype)}

    def _update(self, param, grad, state, lr):
        if self._weight_decay:
            grad = grad + self._weight_decay * param
        ms = self._rho * state["mean_square"] + (1 - self._rho) * \
            jnp.square(grad)
        if self._centered:
            mg = self._rho * state["mean_grad"] + (1 - self._rho) * grad
            denom = jnp.sqrt(ms - jnp.square(mg) + self._epsilon)
        else:
            mg = state["mean_grad"]
            denom = jnp.sqrt(ms + self._epsilon)
        v = self._momentum * state["velocity"] + lr * grad / denom
        return param - v, {"mean_square": ms, "mean_grad": mg, "velocity": v}


class Adadelta(Optimizer):
    _elementwise_rule = True
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._rho, self._epsilon = rho, epsilon

    def _init_state(self, param):
        shape = param.shape if hasattr(param, "shape") else ()
        dtype = param._data.dtype if isinstance(param, Tensor) else \
            param.dtype
        return {"avg_squared_grad": jnp.zeros(shape, dtype),
                "avg_squared_update": jnp.zeros(shape, dtype)}

    def _update(self, param, grad, state, lr):
        if self._weight_decay:
            grad = grad + self._weight_decay * param
        rho, eps = self._rho, self._epsilon
        asg = rho * state["avg_squared_grad"] + (1 - rho) * jnp.square(grad)
        update = grad * jnp.sqrt(state["avg_squared_update"] + eps) / \
            jnp.sqrt(asg + eps)
        asu = rho * state["avg_squared_update"] + (1 - rho) * \
            jnp.square(update)
        return param - lr * update, {"avg_squared_grad": asg,
                                     "avg_squared_update": asu}


class Lamb(Optimizer):
    """reference: operators/optimizers/lamb_op.cc (layer-wise adaptation)."""

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01,
                 beta1=0.9, beta2=0.999, epsilon=1e-6, parameters=None,
                 grad_clip=None, exclude_from_weight_decay_fn=None,
                 name=None):
        super().__init__(learning_rate, parameters, lamb_weight_decay,
                         grad_clip)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        # paddle passes the Parameter object to the predicate
        self._exclude_fn = exclude_from_weight_decay_fn
        self._exclude_ids = None

    def _decay_for_name(self, name):
        """jit/tree path: predicate gets the pytree param name (the eager
        path passes the Parameter object, matching paddle)."""
        if self._exclude_fn is None:
            return True
        try:
            return not bool(self._exclude_fn(name))
        except Exception:
            return True

    def step(self):
        if self._exclude_fn is not None:
            self._exclude_ids = {
                id(p._data) for p in self._params()
                if self._exclude_fn(p)}
        super().step()
        self._exclude_ids = None

    def _init_state(self, param):
        shape = param.shape if hasattr(param, "shape") else ()
        dtype = param._data.dtype if isinstance(param, Tensor) else \
            param.dtype
        mdtype = jnp.float32 if dtype in (jnp.bfloat16, jnp.float16) \
            else dtype
        return {"moment1": jnp.zeros(shape, mdtype),
                "moment2": jnp.zeros(shape, mdtype),
                "beta1_pow": jnp.ones([], jnp.float32),
                "beta2_pow": jnp.ones([], jnp.float32)}

    def _update(self, param, grad, state, lr, decay_on=True):
        b1, b2, eps = self._beta1, self._beta2, self._epsilon
        g = grad.astype(state["moment1"].dtype)
        m = b1 * state["moment1"] + (1 - b1) * g
        v = b2 * state["moment2"] + (1 - b2) * jnp.square(g)
        b1p = state["beta1_pow"] * b1
        b2p = state["beta2_pow"] * b2
        m_hat = m / (1 - b1p)
        v_hat = v / (1 - b2p)
        r = m_hat / (jnp.sqrt(v_hat) + eps)
        excluded = (self._exclude_ids is not None
                    and id(param) in self._exclude_ids)
        if decay_on and self._weight_decay and not excluded:
            r = r + self._weight_decay * param.astype(r.dtype)
        w_norm = jnp.linalg.norm(param.astype(jnp.float32))
        r_norm = jnp.linalg.norm(r.astype(jnp.float32))
        trust = jnp.where(
            (w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        new_param = (param.astype(r.dtype) - lr * trust * r).astype(
            param.dtype)
        return new_param, {"moment1": m, "moment2": v, "beta1_pow": b1p,
                           "beta2_pow": b2p}


class LarsMomentum(Momentum):
    """reference: operators/optimizers/lars_momentum_op.cc"""
    _elementwise_rule = False  # per-param trust ratio

    def __init__(self, learning_rate=0.001, momentum=0.9,
                 lars_coeff=0.001, lars_weight_decay=0.0005,
                 parameters=None, grad_clip=None, epsilon=1e-9, name=None):
        super().__init__(learning_rate, momentum, parameters,
                         weight_decay=None, grad_clip=grad_clip)
        self._lars_coeff = lars_coeff
        self._lars_wd = lars_weight_decay
        self._eps = epsilon

    def _update(self, param, grad, state, lr):
        w_norm = jnp.linalg.norm(param.astype(jnp.float32))
        g_norm = jnp.linalg.norm(grad.astype(jnp.float32))
        local_lr = jnp.where(
            (w_norm > 0) & (g_norm > 0),
            lr * self._lars_coeff * w_norm
            / (g_norm + self._lars_wd * w_norm + self._eps), lr)
        g = grad + self._lars_wd * param
        v = self._momentum * state["velocity"] + local_lr * g
        return param - v, {"velocity": v}


Lars = LarsMomentum
