"""Communication-reducing meta-optimizers: LocalSGD, DGC, fp16 allreduce.

Reference parity: ``fleet/meta_optimizers/localsgd_optimizer.py`` (k local
steps then parameter averaging), ``dgc_optimizer.py`` + ``dgc_op.cc``
(Deep Gradient Compression: top-k sparsified momentum-corrected allreduce
with local residual accumulation), ``fp16_allreduce_optimizer.py`` (cast
grads to fp16 for the wire).

TPU-native design: the reference expresses "per-rank" state through
separate processes + NCCL ops.  Under SPMD there are no per-rank programs,
so per-rank divergence is made explicit: parameters/gradients/compression
state carry a leading ``[dp]`` axis sharded over the data axis
(``PartitionSpec('dp')`` → one slice per device), and the local step is
``jax.vmap``-ed over it.  Cross-rank communication (the allreduce) is a
mean over that axis — XLA lowers it to the same ICI collective an explicit
psum would be.  This keeps the exact semantics (local momentum, residuals,
divergent local params between syncs) testable on a host-device mesh.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding

from ...core.tensor import Tensor
from ...core import autograd, rng as rng_mod
from ...jit import functional_call
from .. import mesh as mesh_mod

DATA_AXES = ("dp", "sharding")


class _PerRankStep:
    """Shared machinery: flat params, [dp]-stacked state, compile cache."""

    def __init__(self, model, optimizer, loss_fn=None, mesh=None,
                 stack_params=False):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.mesh = mesh or mesh_mod.ensure_mesh()
        self.dp = 1
        for ax in DATA_AXES:
            self.dp *= self.mesh.shape.get(ax, 1)
        self.stack_params = stack_params

        params = dict(model.named_parameters())
        self.pnames = sorted(k for k in params if params[k].trainable)
        self.frozen = {k: params[k]._data for k in params
                       if not params[k].trainable}
        self.buffers = {k: v._data for k, v in model.named_buffers()
                        if v is not None}
        rank_spec = NamedSharding(self.mesh, P(DATA_AXES))

        def stack(a):
            return jax.device_put(
                jnp.broadcast_to(a[None], (self.dp,) + a.shape), rank_spec)

        if stack_params:
            self.params = {k: stack(params[k]._data) for k in self.pnames}
            self.opt_state = {
                k: jax.tree_util.tree_map(
                    stack, optimizer._init_state(params[k]))
                for k in self.pnames}
        else:
            self.params = {k: jax.device_put(
                params[k]._data, NamedSharding(self.mesh, P()))
                for k in self.pnames}
            self.opt_state = {k: optimizer._init_state(params[k])
                              for k in self.pnames}
        self._stack = stack
        self._compiled = {}

    # -- pure forward/loss over one rank's arrays -----------------------
    def _loss(self, p_dict, inputs, labels, key):
        full = dict(p_dict)
        full.update(self.frozen)
        with autograd.no_grad():
            out, _ = functional_call(
                self.model, full, dict(self.buffers), inputs,
                training=True, rng_key=key)
        if isinstance(out, tuple):
            out = out[0]
        if self.loss_fn is None:
            loss = out
        else:
            loss = self.loss_fn(Tensor(out), *[Tensor(l) for l in labels])
        loss = loss._data if isinstance(loss, Tensor) else loss
        return loss.astype(jnp.float32)

    def _shard_batch(self, arrays):
        return [a.reshape((self.dp, -1) + a.shape[1:]) for a in arrays]

    # -- state protocol: subclasses with extra per-rank state override ---
    def _state_tuple(self):
        return (self.params, self.opt_state)

    def _set_state_tuple(self, states):
        self.params, self.opt_state = states

    # -- public step ----------------------------------------------------
    def step(self, inputs, labels=()):
        inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        labels = labels if isinstance(labels, (list, tuple)) else [labels]
        ins = [x._data if isinstance(x, Tensor) else jnp.asarray(x)
               for x in inputs]
        labs = [x._data if isinstance(x, Tensor) else jnp.asarray(x)
                for x in labels]
        key = rng_mod.next_key()
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        sig = tuple((tuple(a.shape), str(a.dtype)) for a in ins + labs)
        if sig not in self._compiled:
            self._compiled[sig] = jax.jit(self._build())
        with mesh_mod.compiling_for(self.mesh):
            loss, *new_states = self._compiled[sig](
                *self._state_tuple(), lr, key, ins, labs)
        self._set_state_tuple(new_states)
        self.optimizer._step_count += 1
        return Tensor(loss)

    def sync_to_layer(self):
        named = dict(self.model.named_parameters())
        for k in self.pnames:
            arr = self.params[k]
            if self.stack_params:
                arr = arr.mean(axis=0) if jnp.issubdtype(
                    arr.dtype, jnp.floating) else arr[0]
            named[k]._data = jax.device_put(
                np.asarray(arr), next(iter(self.mesh.devices.flat)))


class LocalSGDStep(_PerRankStep):
    """k local optimizer steps per rank, then parameter averaging
    (reference: localsgd_optimizer.py LocalSGDOptimizer; the adaptive
    variant is AdaptiveLocalSGDStep below)."""

    def __init__(self, model, optimizer, loss_fn=None, mesh=None,
                 k_steps=2):
        super().__init__(model, optimizer, loss_fn, mesh,
                         stack_params=True)
        self.k_steps = max(int(k_steps), 1)

    def _build(self):
        pnames, k_steps, dp = self.pnames, self.k_steps, self.dp
        opt = self.optimizer

        def step(params, opt_state, lr, key, ins, labs):
            ins_r = self._shard_batch(ins)
            labs_r = self._shard_batch(labs)
            ranks = jnp.arange(dp)

            def local(rank, p, s, mb, lab):
                for i in range(k_steps):
                    c_in = [a.reshape((k_steps, -1) + a.shape[1:])[i]
                            for a in mb]
                    c_lab = [a.reshape((k_steps, -1) + a.shape[1:])[i]
                            for a in lab]
                    kk = jax.random.fold_in(jax.random.fold_in(key, rank),
                                            i)
                    loss, g = jax.value_and_grad(
                        lambda pp: self._loss(
                            dict(zip(pnames, [pp[k2] for k2 in pnames])),
                            c_in, c_lab, kk))(p)
                    p, s = opt.apply_gradients_tree(p, g, s, lr)
                return loss, p, s

            losses, new_p, new_s = jax.vmap(local)(
                ranks, params, opt_state, ins_r, labs_r)
            # parameter sync: average over ranks, re-broadcast
            synced = {k: jnp.broadcast_to(
                new_p[k].mean(axis=0)[None], new_p[k].shape)
                for k in pnames}
            return losses.mean(), synced, new_s

        return step


class AdaptiveLocalSGDStep(_PerRankStep):
    """LocalSGD with an adaptive communication interval.

    Reference parity: ``AdaptiveLocalSGDOptimizer``
    (``fleet/meta_optimizers/localsgd_optimizer.py:195``): every iteration
    is one local step per rank; parameters are averaged when
    ``step - last_sync >= k``, and after each sync the next interval is
    ``clip(ceil(sqrt(lr_0 * loss / (lr * loss_0) * init_k_steps)), 1, 16)``
    (``:417-433``) with ``lr_0``/``loss_0`` captured at the first step
    (``:353-357``).  The interval logic runs on the host (it is control
    flow between compiled programs, not inside one), so only two programs
    ever compile: the local step and the sync.
    """

    def __init__(self, model, optimizer, loss_fn=None, mesh=None,
                 init_k_steps=1, begin_step=1, max_k_steps=16):
        super().__init__(model, optimizer, loss_fn, mesh,
                         stack_params=True)
        self.init_k_steps = max(int(init_k_steps), 1)
        self.k_steps = self.init_k_steps
        self.begin_step = max(int(begin_step), 1)
        self.max_k_steps = max(int(max_k_steps), 1)
        self._iter = 0
        self._last_sync = 0
        self._loss0 = None
        self._lr0 = None
        self._sync_fn = None

    def _build(self):
        pnames, dp = self.pnames, self.dp
        opt = self.optimizer

        def step(params, opt_state, lr, key, ins, labs):
            ins_r = self._shard_batch(ins)
            labs_r = self._shard_batch(labs)
            ranks = jnp.arange(dp)

            def local(rank, p, s, mb, lab):
                kk = jax.random.fold_in(key, rank)
                loss, g = jax.value_and_grad(
                    lambda pp: self._loss(
                        dict(zip(pnames, [pp[k2] for k2 in pnames])),
                        mb, lab, kk))(p)
                p, s = opt.apply_gradients_tree(p, g, s, lr)
                return loss, p, s

            losses, new_p, new_s = jax.vmap(local)(
                ranks, params, opt_state, ins_r, labs_r)
            return losses.mean(), new_p, new_s

        return step

    def _sync_params(self):
        if self._sync_fn is None:
            pnames = self.pnames

            def sync(params):
                return {
                    k: jnp.broadcast_to(
                        params[k].mean(axis=0)[None], params[k].shape)
                    if jnp.issubdtype(params[k].dtype, jnp.floating)
                    else params[k]
                    for k in pnames}

            self._sync_fn = jax.jit(sync, donate_argnums=(0,))
        self.params = self._sync_fn(self.params)

    def step(self, inputs, labels=()):
        loss = super().step(inputs, labels)
        self._iter += 1
        lr = max(float(self.optimizer.get_lr()), 1e-12)
        if self._loss0 is None:
            # one host sync at step 1 to anchor loss_0/lr_0 (reference
            # `initialize` branch); steps between syncs stay async
            self._loss0 = max(float(loss.numpy()), 1e-12)
            self._lr0 = lr
        if (self._iter >= self.begin_step
                and self._iter - self._last_sync >= self.k_steps):
            self._sync_params()
            self._last_sync = self._iter
            loss_val = max(float(loss.numpy()), 0.0)
            ratio = (self._lr0 * loss_val) / (lr * self._loss0)
            self.k_steps = int(np.clip(
                np.ceil(np.sqrt(ratio * self.init_k_steps)),
                1, self.max_k_steps))
        return loss


class DGCStep(_PerRankStep):
    """Deep Gradient Compression (reference: dgc_op.cc, dgc_momentum_op,
    sparse_all_reduce_op_handle.cc): per-rank momentum correction, top-k
    selection by magnitude, residual (unsent) accumulation, allreduce of
    the sparse gradients.  On TPU the "sparse send" is a masked dense mean
    over the rank axis (ICI bandwidth makes dense collectives the fast
    path; the *optimization semantics* — what the reference's GPUs compute
    — are preserved exactly)."""

    def __init__(self, model, optimizer, loss_fn=None, mesh=None,
                 sparsity=0.9, momentum=0.9):
        super().__init__(model, optimizer, loss_fn, mesh,
                         stack_params=False)
        self.sparsity = float(sparsity)
        self.momentum = float(momentum)
        # per-rank compression state: u (momentum), v (residual)
        rank_spec = NamedSharding(self.mesh, P(DATA_AXES))
        self.dgc_state = {
            k: {"u": jax.device_put(
                    jnp.zeros((self.dp,) + self.params[k].shape,
                              jnp.float32), rank_spec),
                "v": jax.device_put(
                    jnp.zeros((self.dp,) + self.params[k].shape,
                              jnp.float32), rank_spec)}
            for k in self.pnames}

    def _state_tuple(self):
        return (self.params, self.opt_state, self.dgc_state)

    def _set_state_tuple(self, states):
        self.params, self.opt_state, self.dgc_state = states

    def _build(self):
        pnames, dp = self.pnames, self.dp
        m, sparsity = self.momentum, self.sparsity
        opt = self.optimizer

        def topk_mask(v):
            flat = jnp.abs(v).reshape(-1)
            keep = max(int(flat.size * (1.0 - sparsity)), 1)
            thresh = jax.lax.top_k(flat, keep)[0][-1]
            return (jnp.abs(v) >= thresh).astype(v.dtype)

        def step(params, opt_state, dgc_state, lr, key, ins, labs):
            ins_r = self._shard_batch(ins)
            labs_r = self._shard_batch(labs)
            ranks = jnp.arange(dp)

            def local_grads(rank, mb, lab):
                kk = jax.random.fold_in(key, rank)
                loss, g = jax.value_and_grad(
                    lambda pp: self._loss(
                        dict(zip(pnames, [pp[k2] for k2 in pnames])),
                        mb, lab, kk))(params)
                return loss, g

            losses, grads_stacked = jax.vmap(
                local_grads, in_axes=(0, 0, 0))(ranks, ins_r, labs_r)

            new_params, new_opt, new_dgc = {}, {}, {}
            for k in pnames:
                g = grads_stacked[k]                    # [dp, ...]
                st = dgc_state[k]
                u = m * st["u"] + g                     # momentum corr.
                v = st["v"] + u                         # residual acc.
                mask = jax.vmap(topk_mask)(v)           # per-rank top-k
                send = v * mask
                new_dgc[k] = {"u": u * (1 - mask), "v": v * (1 - mask)}
                g_sync = send.mean(axis=0)              # the "allreduce"
                new_params[k], new_opt[k] = opt._update(
                    params[k], g_sync, opt_state[k], lr)
            return losses.mean(), new_params, new_opt, new_dgc

        return step


class FP16AllReduceStep(_PerRankStep):
    """Cast per-rank grads to fp16 before the cross-rank mean, back to f32
    after (reference: fp16_allreduce_optimizer.py — halves wire bytes;
    numerics match the reference's pre-allreduce cast exactly)."""

    def _build(self):
        pnames, dp = self.pnames, self.dp
        opt = self.optimizer

        def step(params, opt_state, lr, key, ins, labs):
            ins_r = self._shard_batch(ins)
            labs_r = self._shard_batch(labs)
            ranks = jnp.arange(dp)

            def local_grads(rank, mb, lab):
                kk = jax.random.fold_in(key, rank)
                loss, g = jax.value_and_grad(
                    lambda pp: self._loss(
                        dict(zip(pnames, [pp[k2] for k2 in pnames])),
                        mb, lab, kk))(params)
                return loss, jax.tree_util.tree_map(
                    lambda a: a.astype(jnp.float16), g)

            losses, g16 = jax.vmap(local_grads)(ranks, ins_r, labs_r)
            new_params, new_opt = {}, {}
            for k in pnames:
                g = g16[k].astype(jnp.float32).mean(axis=0)
                new_params[k], new_opt[k] = opt._update(
                    params[k], g, opt_state[k], lr)
            return losses.mean(), new_params, new_opt

        return step
