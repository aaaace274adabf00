"""Sharded, compiled training steps.

Reference parity: this single builder replaces the reference's execution
stack — ParallelExecutor + SSA graph executors
(``parallel_executor.cc:609``, ``fast_threaded_ssa_graph_executor.cc:59``),
the dygraph DDP Reducer (``reducer.cc:270``), the fleet meta-optimizer
program rewrites (sharding/amp/recompute/gradient-merge), and the fused
optimizer passes.  One pjit'd function computes forward, backward, gradient
reduction (implicit via shardings), and the optimizer update; XLA schedules
compute/collective overlap that the reference hand-built with op handles
and comm streams.

Strategy mapping (DistributedStrategy -> jax):
  dp/sharding axes  -> batch PartitionSpec(('dp','sharding'))
  sharding stage 2  -> optimizer-state specs sharded, params replicated
  sharding stage 3  -> parameter specs sharded (ZeRO-3 / FSDP)
  mp                -> explicit per-param specs from TP layers
  pp                -> stacked-block pipeline (parallel/pipeline.py)
  amp               -> bf16 autocast inside the traced step
  gradient_merge    -> lax.scan micro-batch accumulation
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding

from ..core.tensor import Tensor
from ..core import autograd, rng as rng_mod
from ..jit import functional_call
from ..distributed import mesh as mesh_mod
from ..distributed.sharding import shard_params_specs
from .. import amp as amp_mod

DATA_AXES = mesh_mod.DATA_AXES  # single source: distributed/mesh.py


def _batch_spec(ndim):
    return P(DATA_AXES, *([None] * (ndim - 1)))


def _state_spec_like(param_spec, leaf):
    """Optimizer-state leaf adopts its param's spec when shapes match."""
    if leaf.ndim == 0:
        return P()
    return param_spec


class TrainStep:
    """Compiled train step over a Layer + Optimizer (+ loss)."""

    def __init__(self, model, optimizer, loss_fn=None, strategy=None,
                 mesh=None, amp_level=None, donate=True, train=True,
                 metrics=None):
        from ..distributed.parallel import DataParallel
        from ..distributed.fleet.meta_parallel import PipelineLayer
        if isinstance(model, DataParallel):
            model = model._layers
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.strategy = strategy
        self.mesh = mesh or mesh_mod.ensure_mesh()
        self.donate = donate
        self.training = train
        # metrics computed INSIDE the compiled step (reference:
        # hapi/model.py:1495 threads prepared metrics through train);
        # each step stashes the per-batch metric inputs (e.g. Accuracy's
        # correct matrix) in self.last_metric_outs
        self.metrics = list(metrics or [])
        self.last_metric_outs = []
        self._compiled = {}

        s = strategy
        self.use_amp = bool(amp_level) or bool(s and s.amp)
        self.amp_level = amp_level or (
            "O2" if (s and s.amp_configs.get("use_pure_fp16")) else "O1")
        self.grad_merge_k = 1
        if s and s.gradient_merge:
            self.grad_merge_k = int(
                s.gradient_merge_configs.get("k_steps", 1))

        # metric handles resolved once — step() is the hot path
        from .. import monitor
        self._m_steps = monitor.counter("train.steps",
                                        "TrainStep.step calls")
        self._m_step_time = monitor.histogram(
            "train.step_time_ms",
            "host-side dispatch time per train step (ms)")

        self.is_pipeline = isinstance(model, PipelineLayer) and \
            self.mesh.shape.get("pp", 1) > 1
        if self.is_pipeline:
            self._init_pipeline_state()
        else:
            self._init_flat_state()

    # ------------------------------------------------------------------
    def _stage(self):
        s = self.strategy
        if s is not None and s.sharding:
            return int(s.sharding_configs.get("stage", 2))
        return 0

    def _init_flat_state(self):
        params = dict(self.model.named_parameters())
        buffers = {k: v for k, v in self.model.named_buffers()
                   if v is not None}
        self.pnames = sorted(params)
        self.bnames = sorted(buffers)
        stage = self._stage()
        min_size = 1024
        if self.strategy is not None:
            min_size = int(self.strategy.sharding_configs.get(
                "min_shard_size", 1024))
        spec_map = shard_params_specs(
            self.model, stage=stage if stage else 2,
            axis="sharding", min_size=min_size)
        if stage < 3:
            # stages 0-2: params replicated unless TP says otherwise
            for k in self.pnames:
                if getattr(params[k], "partition_spec", None) is None:
                    spec_map[k] = P()
        self.param_specs = {k: spec_map.get(k, P()) for k in self.pnames}

        # the flat-slab fused optimizer update concatenates params into
        # one vector — only sound when params are REPLICATED (dp).
        # Under TP/FSDP shardings the concat would force all-gathers of
        # every shard each step; keep the per-param path there (those
        # updates are already shard-local).  Passed per-call (no
        # mutation of the caller's optimizer)
        self._fuse_opt = None  # optimizer's own setting
        if any(spec != P() for spec in self.param_specs.values()):
            # unconditional (not gated on the optimizer's CURRENT
            # fuse_update): flipping opt.fuse_update=True after
            # construction must not re-enable the slab path for
            # sharded params
            if getattr(self.optimizer, "fuse_update", False):
                import logging
                logging.getLogger("paddle_tpu").info(
                    "fuse_update disabled for this TrainStep: params are "
                    "sharded (TP/FSDP); the fused flat-slab update applies "
                    "to replicated-param regimes only")
            self._fuse_opt = False

        self.params = {}
        for k in self.pnames:
            arr = params[k]._data
            self.params[k] = jax.device_put(
                arr, NamedSharding(self.mesh, self.param_specs[k]))
        self.buffers = {k: jax.device_put(
            buffers[k]._data, NamedSharding(self.mesh, P()))
            for k in self.bnames}

        self.opt_state = {k: self.optimizer._init_state(params[k])
                          for k in self.pnames}
        # ZeRO stage >= 1: shard optimizer moments over 'sharding'
        self.opt_specs = {}
        shard_world = self.mesh.shape.get("sharding", 1)
        for k in self.pnames:
            pspec = self.param_specs[k]
            sub = {}
            for sk, leaf in self.opt_state[k].items():
                if leaf.ndim == 0:
                    sub[sk] = P()
                elif stage >= 1 and shard_world > 1 and \
                        pspec == P() and leaf.shape and \
                        leaf.shape[0] % shard_world == 0:
                    sub[sk] = P("sharding")
                else:
                    sub[sk] = _state_spec_like(pspec, leaf)
            self.opt_specs[k] = sub
        self.opt_state = {
            k: {sk: jax.device_put(leaf, NamedSharding(
                self.mesh, self.opt_specs[k][sk]))
                for sk, leaf in sub.items()}
            for k, sub in self.opt_state.items()}
        self._trainable = {k: params[k].trainable for k in self.pnames}

    def _init_pipeline_state(self):
        from .pipeline import (stack_block_params, stack_block_buffers,
                               build_pipeline_fn, build_pipeline_1f1b_fn)
        model = self.model
        pp = self.mesh.shape.get("pp", 1)
        nblocks = len(model.blocks)
        assert nblocks % pp == 0, \
            f"n_blocks {nblocks} must divide pp degree {pp}"
        self.bps = nblocks // pp
        self.block_pnames, stacked = stack_block_params(model.blocks)
        self.block_bnames, stacked_bufs = stack_block_buffers(model.blocks)
        # regroup [nblocks, ...] -> [pp, bps, ...]
        self.block_params = {
            k: jax.device_put(
                v.reshape((pp, self.bps) + v.shape[1:]),
                NamedSharding(self.mesh, P("pp")))
            for k, v in stacked.items()}
        self.block_buffers = {
            k: jax.device_put(
                v.reshape((pp, self.bps) + v.shape[1:]),
                NamedSharding(self.mesh, P("pp")))
            for k, v in stacked_bufs.items()}
        self.pre_params = {}
        self.post_params = {}
        if model.pre is not None:
            self.pre_params = {k: jax.device_put(
                p._data, NamedSharding(
                    self.mesh, getattr(p, "partition_spec", None) or P()))
                for k, p in dict(model.pre.named_parameters()).items()}
        if model.post is not None:
            self.post_params = {k: jax.device_put(
                p._data, NamedSharding(
                    self.mesh, getattr(p, "partition_spec", None) or P()))
                for k, p in dict(model.post.named_parameters()).items()}
        M = 1
        schedule = "F-then-B"
        if self.strategy is not None and self.strategy.pipeline:
            M = int(self.strategy.pipeline_configs.get(
                "accumulate_steps", 1))
            schedule = str(self.strategy.pipeline_configs.get(
                "schedule_mode",
                self.strategy.pipeline_configs.get("schedule",
                                                   "F-then-B")))
        self.num_microbatches = max(M, 1)
        self.pipe_schedule = "1F1B" if schedule.upper() == "1F1B" \
            else "F-then-B"
        use_remat = bool(self.strategy and self.strategy.recompute)
        if self.pipe_schedule == "1F1B":
            self.pipe_1f1b, _, _ = build_pipeline_1f1b_fn(
                model, self.num_microbatches, self.loss_fn,
                mesh=self.mesh, training=self.training)
            self.pipe_fn = None
        else:
            self.pipe_fn, _, _ = build_pipeline_fn(
                model, self.num_microbatches, mesh=self.mesh,
                training=self.training, use_recompute=use_remat)
            self.pipe_1f1b = None
        # one flat param tree for the optimizer
        self.params = {"pre": self.pre_params, "block": self.block_params,
                       "post": self.post_params}
        self.opt_state = jax.tree_util.tree_map(
            lambda a: self.optimizer._init_state(Tensor(a)), self.params,
            is_leaf=lambda x: isinstance(x, jax.Array))
        self.buffers = {}
        self.bnames = []

    # ------------------------------------------------------------------
    def _loss_from_out(self, out, labels):
        with autograd.no_grad():
            if self.loss_fn is None:
                loss_t = out if isinstance(out, Tensor) else Tensor(out)
            else:
                wrapped_out = Tensor(out) if not isinstance(out, Tensor) \
                    else out
                wrapped_labels = [Tensor(l) for l in labels]
                loss_t = self.loss_fn(wrapped_out, *wrapped_labels)
            return loss_t._data if isinstance(loss_t, Tensor) else loss_t

    def _build_flat(self, in_shapes):
        model = self.model
        pnames, bnames = self.pnames, self.bnames
        training = self.training
        use_amp, amp_level = self.use_amp, self.amp_level
        merge_k = self.grad_merge_k

        metrics = self.metrics

        def forward_loss(p_arrays, b_arrays, inputs, labels, key):
            import contextlib
            ctx = amp_mod.auto_cast(
                enable=True, level=amp_level) if use_amp else \
                contextlib.nullcontext()
            with ctx:
                with autograd.no_grad():
                    out, new_buf = functional_call(
                        model, dict(zip(pnames, p_arrays)),
                        dict(zip(bnames, b_arrays)), inputs,
                        training=training, rng_key=key)
                if isinstance(out, tuple):
                    out = out[0]
                loss = self._loss_from_out(out, labels)
                # expert-parallel models: add MoE load-balancing aux loss
                # (already scaled by each layer's aux_weight)
                from ..distributed.moe import collect_moe_aux_loss
                aux = collect_moe_aux_loss(model)
                if aux is not None:
                    # loss is a raw array here (see _loss_from_out)
                    loss = loss + (aux._data if isinstance(aux, Tensor)
                                   else aux)
                metric_outs = []
                if metrics:
                    with autograd.no_grad():
                        out_t = out if isinstance(out, Tensor) \
                            else Tensor(out)
                        lab_t = [Tensor(l) for l in labels]
                        for m in metrics:
                            mo = m.compute(out_t, *lab_t)
                            mo = mo if isinstance(mo, (list, tuple)) \
                                else [mo]
                            metric_outs.append(
                                [x._data if isinstance(x, Tensor) else x
                                 for x in mo])
            return loss.astype(jnp.float32), (
                [new_buf[k] for k in bnames], metric_outs)

        trainable = self._trainable

        def step(params, buffers, opt_state, lr, key, inputs, labels):
            p_list = [params[k] for k in pnames]
            b_list = [buffers[k] for k in bnames]

            def loss_of(p_sub):
                merged = [p_sub[k] if trainable[k] else params[k]
                          for k in pnames]
                return forward_loss(merged, b_list, inputs, labels, key)

            p_sub = {k: params[k] for k in pnames if trainable[k]}
            if merge_k > 1:
                def micro(i, acc):
                    g_acc, l_acc, buf = acc
                    mb_in = [a.reshape((merge_k, -1) + a.shape[1:])[i]
                             for a in inputs]
                    mb_lab = [a.reshape((merge_k, -1) + a.shape[1:])[i]
                              for a in labels]

                    def loss_mb(p_sub2):
                        merged = [p_sub2[k] if trainable[k] else params[k]
                                  for k in pnames]
                        return forward_loss(merged, b_list, mb_in, mb_lab,
                                            jax.random.fold_in(key, i))

                    (l, (buf2, mo)), g = jax.value_and_grad(
                        loss_mb, has_aux=True)(p_sub)
                    g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                    return (g_acc, l_acc + l, buf2), mo

                # unrolled python loop (merge_k is small & static)
                zero_g = jax.tree_util.tree_map(jnp.zeros_like, p_sub)
                g_acc, l_acc, buf = zero_g, jnp.zeros([], jnp.float32), \
                    b_list
                metric_parts = []
                for i in range(merge_k):
                    (g_acc, l_acc, buf), mo = micro(
                        i, (g_acc, l_acc, buf))
                    metric_parts.append(mo)
                grads = jax.tree_util.tree_map(
                    lambda g: g / merge_k, g_acc)
                loss = l_acc / merge_k
                new_b_list = buf
                # combine per-micro metric inputs: batch-dim concat for
                # arrays, stack for scalars (all microbatches reach
                # m.update(); taking only the last would drop 1-1/k of
                # the batch)
                metric_outs = []
                if metric_parts and metric_parts[0]:
                    for mi in range(len(metric_parts[0])):
                        metric_outs.append([
                            jnp.concatenate(
                                [mp[mi][j] for mp in metric_parts])
                            if metric_parts[0][mi][j].ndim else
                            jnp.stack([mp[mi][j] for mp in metric_parts])
                            for j in range(len(metric_parts[0][mi]))])
            else:
                (loss, (new_b_list, metric_outs)), grads = \
                    jax.value_and_grad(loss_of, has_aux=True)(p_sub)

            new_sub, new_opt_sub = self.optimizer.apply_gradients_tree(
                p_sub, grads,
                {k: opt_state[k] for k in p_sub}, lr,
                fuse=self._fuse_opt)
            new_params = dict(params)
            new_params.update(new_sub)
            new_opt = dict(opt_state)
            new_opt.update(new_opt_sub)
            # re-pin shardings so XLA keeps the layout stable
            new_params = {
                k: jax.lax.with_sharding_constraint(
                    v, NamedSharding(self.mesh, self.param_specs[k]))
                for k, v in new_params.items()}
            new_buffers = dict(zip(bnames, new_b_list))
            return loss, new_params, new_buffers, new_opt, metric_outs

        batch_sharding = self._data_sharding

        in_shardings = (
            {k: NamedSharding(self.mesh, self.param_specs[k])
             for k in pnames},
            {k: NamedSharding(self.mesh, P()) for k in bnames},
            {k: {sk: NamedSharding(self.mesh, self.opt_specs[k][sk])
                 for sk in self.opt_specs[k]} for k in pnames},
            NamedSharding(self.mesh, P()),
            NamedSharding(self.mesh, P()),
            [batch_sharding(s) for s in in_shapes[1]],
            [batch_sharding(s) for s in in_shapes[2]],
        )
        donate = (0, 2) if self.donate else ()
        return jax.jit(step, in_shardings=in_shardings,
                       donate_argnums=donate)

    def _build_pipeline(self, in_shapes):
        if self.pipe_schedule == "1F1B":
            return self._build_pipeline_1f1b(in_shapes)
        pipe_fn = self.pipe_fn

        metrics = self.metrics

        def step(params, buffers, opt_state, lr, key, inputs, labels):
            def loss_of(p):
                out, new_bufs = pipe_fn(p["pre"], p["block"], p["post"],
                                        inputs[0], key,
                                        block_buffers=buffers)
                loss = self._loss_from_out(out, labels).astype(
                    jnp.float32)
                metric_outs = []
                if metrics:
                    with autograd.no_grad():
                        out_t = Tensor(out)
                        lab_t = [Tensor(l) for l in labels]
                        for m in metrics:
                            mo = m.compute(out_t, *lab_t)
                            mo = mo if isinstance(mo, (list, tuple)) \
                                else [mo]
                            metric_outs.append(
                                [x._data if isinstance(x, Tensor) else x
                                 for x in mo])
                return loss, (new_bufs, metric_outs)

            (loss, (new_bufs, metric_outs)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params)
            # pipeline: block params are pp-sharded stacks — the flat
            # concat would all-gather them; never fuse here
            new_params, new_opt = self.optimizer.apply_gradients_tree(
                params, grads, opt_state, lr, fuse=False)
            return loss, new_params, new_bufs, new_opt, metric_outs

        donate = (0, 2) if self.donate else ()
        return jax.jit(step, donate_argnums=donate)

    def _build_pipeline_1f1b(self, in_shapes):
        pipe_1f1b = self.pipe_1f1b

        def step(params, buffers, opt_state, lr, key, inputs, labels):
            if self.loss_fn is not None and len(labels) != 1:
                raise ValueError(
                    "1F1B pipeline expects exactly one labels array "
                    f"(got {len(labels)}); GPipe (schedule_mode="
                    "'F-then-B') supports multi-label losses")
            loss, g_pre, g_block, g_post, new_bufs = pipe_1f1b(
                params["pre"], params["block"], params["post"], buffers,
                inputs[0], labels[0] if labels else None, key)
            grads = {"pre": g_pre, "block": g_block, "post": g_post}
            new_params, new_opt = self.optimizer.apply_gradients_tree(
                params, grads, opt_state, lr, fuse=False)
            return loss, new_params, new_bufs, new_opt, []

        if self.metrics:
            import warnings
            warnings.warn(
                "TrainStep(metrics=...) under the 1F1B schedule: the "
                "model output never materializes (loss is consumed "
                "per-microbatch inside the schedule), so in-graph "
                "metrics are not computed — use GPipe "
                "(schedule_mode='F-then-B') or evaluate() for metrics")
        donate = (0, 2) if self.donate else ()
        return jax.jit(step, donate_argnums=donate)

    # ------------------------------------------------------------------
    def _data_sharding(self, shape):
        # non-divisible batches fall back to replicated (correct, just not
        # data-parallel) — policy lives in mesh.batch_partition_spec
        return NamedSharding(self.mesh,
                             mesh_mod.batch_partition_spec(shape,
                                                           self.mesh))

    def _place_inputs(self, inputs, labels):
        """Normalize + place a global batch exactly as the compiled step
        consumes it (single source for step() and aot_compile)."""
        if not isinstance(inputs, (list, tuple)):
            inputs = [inputs]
        if not isinstance(labels, (list, tuple)):
            labels = [labels]
        def _as_array(x):
            # Tensors/jax arrays stay on device; everything else becomes
            # numpy WITHOUT a device commit (placement happens below)
            if isinstance(x, Tensor):
                return x._data
            if isinstance(x, jax.Array):
                return x
            return np.asarray(x)

        in_arrays = [_as_array(x) for x in inputs]
        lab_arrays = [_as_array(x) for x in labels]
        if self.is_pipeline and jax.process_count() > 1:
            # multi-host pipeline: the pp ring may span hosts, so a dp
            # row-block can live on several processes — every process
            # must feed the identical GLOBAL batch (Megatron semantics:
            # ranks within a dp group read the same data) and each cuts
            # out its addressable shards.  Verify the contract once: a
            # per-host local shard fed here would silently train on
            # inconsistent data.
            if not getattr(self, "_mh_feed_checked", False):
                self._mh_feed_checked = True
                import hashlib
                from jax.experimental import multihost_utils
                digest = hashlib.sha256()
                for a in in_arrays + lab_arrays:
                    digest.update(np.ascontiguousarray(a).tobytes())
                h = np.frombuffer(digest.digest()[:8], np.int64)
                gathered = np.asarray(
                    multihost_utils.process_allgather(h))
                if not (gathered == gathered[0]).all():
                    raise ValueError(
                        "multi-host pipeline: processes fed DIFFERENT "
                        "batches. The pp ring spans hosts, so every "
                        "process must feed the identical GLOBAL batch "
                        "(not its local dp shard) — load the same data "
                        "on all ranks of a dp group")
            in_arrays = [mesh_mod.global_from_replicated(a, self.mesh)
                         for a in in_arrays]
            lab_arrays = [mesh_mod.global_from_replicated(a, self.mesh)
                          for a in lab_arrays]
        if not self.is_pipeline:
            if jax.process_count() > 1:
                # multi-host: each process holds its LOCAL batch shard;
                # assemble the global array (reference: per-trainer data
                # partitions feeding one NCCL job)
                in_arrays = [mesh_mod.host_local_to_global(a, self.mesh)
                             for a in in_arrays]
                lab_arrays = [mesh_mod.host_local_to_global(a, self.mesh)
                              for a in lab_arrays]
            else:
                # batches may arrive committed to one device (DataLoader
                # Tensors); re-place them on the mesh so they match the
                # step's declared in_shardings
                in_arrays = [jax.device_put(a, self._data_sharding(a.shape))
                             for a in in_arrays]
                lab_arrays = [jax.device_put(a,
                                             self._data_sharding(a.shape))
                              for a in lab_arrays]
        return in_arrays, lab_arrays

    def step(self, inputs, labels=()):
        """Run one optimization step on a global batch."""
        import time as _time
        t0 = _time.perf_counter()
        in_arrays, lab_arrays = self._place_inputs(inputs, labels)
        key = rng_mod.next_key()
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        shapes_key = (len(in_arrays),
                      tuple(a.ndim for a in in_arrays),
                      tuple(a.ndim for a in lab_arrays),
                      tuple(tuple(a.shape) for a in in_arrays),
                      tuple(tuple(a.shape) for a in lab_arrays))
        if shapes_key not in self._compiled:
            meta = (len(in_arrays), [tuple(a.shape) for a in in_arrays],
                    [tuple(a.shape) for a in lab_arrays])
            if self.is_pipeline:
                self._compiled[shapes_key] = self._build_pipeline(meta)
            else:
                self._compiled[shapes_key] = self._build_flat(meta)
        fn = self._compiled[shapes_key]
        # the first call traces: what is traced reads THIS step's mesh
        with mesh_mod.compiling_for(self.mesh):
            if self.is_pipeline:
                (loss, self.params, self.block_buffers, self.opt_state,
                 self.last_metric_outs) = fn(
                    self.params, self.block_buffers, self.opt_state, lr,
                    key, in_arrays, lab_arrays)
            else:
                (loss, self.params, self.buffers, self.opt_state,
                 self.last_metric_outs) = fn(
                    self.params, self.buffers, self.opt_state, lr, key,
                    in_arrays, lab_arrays)
        self.optimizer._step_count += 1
        # dispatch-side step accounting (monitor registry; the step is
        # async, so the histogram measures host dispatch latency — a
        # compile lands in the first observation's tail bucket)
        self._m_steps.inc()
        self._m_step_time.observe((_time.perf_counter() - t0) * 1e3)
        return Tensor(loss)

    def aot_compile(self, inputs, labels=()):
        """AOT lower + compile the step for these batch shapes WITHOUT
        executing it (jax ahead-of-time API).  Returns
        ``(lowered_seconds, compiled_seconds, compiled)`` — use
        ``compiled.memory_analysis()`` / ``cost_analysis()`` to bound
        HBM and XLA time before committing a real device step.  This is
        the big-model rehearsal path: measure compile on a cheap
        backend before spending chip time on it."""
        import time as _time
        # same placement/global-assembly as step(): the rehearsal must
        # lower the SAME program the real step will compile
        in_arrays, lab_arrays = self._place_inputs(inputs, labels)
        meta = (len(in_arrays), [tuple(a.shape) for a in in_arrays],
                [tuple(a.shape) for a in lab_arrays])
        fn = (self._build_pipeline(meta) if self.is_pipeline
              else self._build_flat(meta))
        # fixed dummy key: the key only shapes the trace, and advancing
        # the global stream from a compile-only rehearsal would silently
        # change every subsequent step's randomness
        key = jax.random.key(0)
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        state = self.block_buffers if self.is_pipeline else self.buffers
        t0 = _time.perf_counter()
        with mesh_mod.compiling_for(self.mesh):
            lowered = fn.lower(self.params, state, self.opt_state, lr, key,
                               in_arrays, lab_arrays)
        t_lower = _time.perf_counter() - t0
        t0 = _time.perf_counter()
        compiled = lowered.compile()
        return t_lower, _time.perf_counter() - t0, compiled

    # ------------------------------------------------------------------
    def sync_to_layer(self):
        """Copy device state back into the Layer's Tensors."""
        if self.is_pipeline:
            from .pipeline import unstack_block_params, \
                unstack_block_buffers
            pp = self.mesh.shape.get("pp", 1)
            flat = {k: np.asarray(v).reshape((-1,) + v.shape[2:])
                    for k, v in self.params["block"].items()}
            unstack_block_params(self.model.blocks, self.block_pnames,
                                 flat)
            flat_b = {k: np.asarray(v).reshape((-1,) + v.shape[2:])
                      for k, v in self.block_buffers.items()}
            unstack_block_buffers(self.model.blocks, self.block_bnames,
                                  flat_b)
            # pre/post params are mesh-committed; re-place on one device
            # so eager eval/predict after training works (same policy as
            # the flat path below)
            dev0 = next(iter(self.mesh.devices.flat))
            for store, params in (("pre", self.params["pre"]),
                                  ("post", self.params["post"])):
                layer = getattr(self.model, store)
                if layer is not None:
                    named = dict(layer.named_parameters())
                    for k, v in params.items():
                        if isinstance(v, jax.Array) and \
                                len(v.devices()) > 1:
                            v = jax.device_put(np.asarray(v), dev0)
                        named[k]._data = v
            return
        # re-place on one device: the Layer copy serves eager eval/predict,
        # where mixing mesh-committed and single-device arrays is an error
        dev = next(iter(self.mesh.devices.flat))

        def _local(a):
            if isinstance(a, jax.Array) and len(a.devices()) > 1:
                return jax.device_put(np.asarray(a), dev)
            return a

        named = dict(self.model.named_parameters())
        for k in self.pnames:
            named[k]._data = _local(self.params[k])
        named_b = dict(self.model.named_buffers())
        for k in self.bnames:
            if k in named_b and named_b[k] is not None:
                named_b[k]._data = _local(self.buffers[k])
