"""SPMD pipeline-parallel engine: GPipe and 1F1B schedules.

Reference parity: PipelineTrainer + SectionWorker
(``framework/trainer.h:325``, ``section_worker.cc:34`` — synchronous GPipe
F-then-B over micro-batch scopes, stages connected by send_v2/recv_v2).

TPU-native design: no per-stage processes, no send/recv ops.  All identical
stage blocks have their parameters STACKED on a leading 'pp'-sharded axis;
ONE shard_map program runs on every device, rotating activations around the
ring with ``lax.ppermute``.  Two schedules:

- **GPipe** (``build_pipeline_fn``): M + P - 1 forward ticks, backward via
  ``jax.grad`` through the rotation (ppermute's transpose is the reverse
  rotation).  Live state O(M) ticks of residuals (O(M) INPUTS with
  per-tick remat).
- **1F1B** (``build_pipeline_1f1b_fn``): hand-scheduled per-tick VJPs.
  Each tick does one masked forward AND one masked backward; cotangents
  rotate on the reverse ring; stage inputs live in a 2P-slot ring buffer,
  so live activations are O(P) — independent of M — at identical math.
  This is the schedule the reference could not express (section_worker is
  F-then-B only) and the VERDICT round-1 item #3.

Buffers (BN running stats) are threaded functionally through both
schedules: forward ticks that process a real microbatch update the
stage's stacked buffer state; backward-pass recomputation reuses, but
does not re-update, the stats.

Heterogeneous ends (embedding / head) run replicated outside the ring.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ..core import autograd, rng as rng_mod
from ..jit import functional_call
from ..distributed import mesh as mesh_mod


def stack_block_params(blocks):
    """blocks: LayerList of structurally-identical Layers ->
    (pnames, {name: stacked [n_blocks, ...]})."""
    pnames = [n for n, _ in blocks[0].named_parameters()]
    stacked = {}
    for name in pnames:
        per_block = []
        for blk in blocks:
            p = dict(blk.named_parameters())[name]
            per_block.append(p._data)
        stacked[name] = jnp.stack(per_block)
    return pnames, stacked


def unstack_block_params(blocks, pnames, stacked):
    for i, blk in enumerate(blocks):
        params = dict(blk.named_parameters())
        for name in pnames:
            params[name]._data = stacked[name][i]


def stack_block_buffers(blocks):
    """Like stack_block_params but for buffers (BN running stats)."""
    bnames = [n for n, b in blocks[0].named_buffers() if b is not None]
    stacked = {}
    for name in bnames:
        stacked[name] = jnp.stack(
            [dict(blk.named_buffers())[name]._data for blk in blocks])
    return bnames, stacked


def unstack_block_buffers(blocks, bnames, stacked):
    for i, blk in enumerate(blocks):
        bufs = dict(blk.named_buffers())
        for name in bnames:
            if bufs.get(name) is not None:
                bufs[name]._data = stacked[name][i]


def _run_stage(template_block, pnames, bnames, stage_params, stage_bufs,
               x, training):
    """Run this device's `bps` consecutive blocks: scan over the block
    axis.  stage_params/stage_bufs leaves: [bps, ...].  Returns
    (h, new_stage_bufs)."""

    n_p = len(pnames)

    def one_block(h, leaves):
        params = dict(zip(pnames, leaves[:n_p]))
        bufs = dict(zip(bnames, leaves[n_p:]))
        out, new_buf = functional_call(template_block, params, bufs, (h,),
                                       training=training)
        return out, [new_buf[k] for k in bnames]

    leaves = [stage_params[n] for n in pnames] + \
        [stage_bufs[n] for n in bnames]
    h, new_buf_stacked = lax.scan(one_block, x, leaves)
    return h, dict(zip(bnames, new_buf_stacked))


def _tree_where(pred, new, old):
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(pred, a, b), new, old)


# ===========================================================================
# GPipe (F-then-B) — backward via jax.grad through the rotation
# ===========================================================================

def build_pipeline_fn(pipe_layer, num_microbatches, mesh=None,
                      training=True, axis="pp", use_recompute=False):
    """Returns (forward, pnames, bnames) where
    ``forward(pre_params, block_stacked, post_params, x_global, key,
    block_buffers) -> (out, new_block_buffers)``.

    block_stacked/block_buffers leaves are [pp, bps, ...] (grouped per
    stage).  x_global: [M * mb, ...] global batch.
    """
    mesh = mesh or mesh_mod.ensure_mesh()
    pp = mesh.shape.get(axis, 1)
    template = pipe_layer.blocks[0]
    pnames = [n for n, _ in template.named_parameters()]
    bnames = [n for n, b in template.named_buffers() if b is not None]
    M = num_microbatches
    run_stage = _run_stage
    if use_recompute:
        # remat each pipeline tick: backward recomputes the stage forward
        # instead of storing M+P-1 ticks of activations (the GPipe memory
        # fix the reference gets from RecomputeOptimizer stacking)
        def run_stage(template, pnames, bnames, stage_params, stage_bufs,
                      x, training):
            fn = jax.checkpoint(
                lambda sp, sb, xx: _run_stage(template, pnames, bnames,
                                              sp, sb, xx, training))
            return fn(stage_params, stage_bufs, x)

    def pipeline_core(stage_params, stage_bufs, h_mbs):
        """Inside shard_map: stage_params leaves [bps, ...] (this stage's
        blocks); h_mbs [M, mb, ...] replicated activations after `pre`."""
        stage = lax.axis_index(axis)
        n = lax.axis_size(axis)
        steps = M + n - 1
        mb_shape = h_mbs.shape[1:]
        out_buf = jnp.zeros((M,) + mb_shape, h_mbs.dtype)
        carry = jnp.zeros(mb_shape, h_mbs.dtype)
        perm = [(i, (i + 1) % n) for i in range(n)]

        def tick(t, state):
            carry, out_buf, bufs = state
            feed_idx = jnp.clip(t, 0, M - 1)
            feed = lax.dynamic_index_in_dim(h_mbs, feed_idx, axis=0,
                                            keepdims=False)
            inp = jnp.where(stage == 0, feed, carry)
            act, new_bufs = run_stage(template, pnames, bnames,
                                      stage_params, bufs, inp, training)
            # running stats advance only on ticks where this stage holds
            # a REAL microbatch (reference: per-microbatch scope BN)
            active = jnp.logical_and(t - stage >= 0, t - stage < M)
            bufs = _tree_where(jnp.logical_and(active, training),
                               new_bufs, bufs)
            # collect at the LAST stage for ticks t in [n-1, n-1+M)
            write_idx = jnp.clip(t - (n - 1), 0, M - 1)
            updated = lax.dynamic_update_index_in_dim(
                out_buf, act, write_idx, axis=0)
            collect = jnp.logical_and(stage == n - 1, t >= n - 1)
            out_buf = jnp.where(collect, updated, out_buf)
            carry_next = lax.ppermute(act, axis, perm)
            return carry_next, out_buf, bufs

        carry, out_buf, stage_bufs = lax.fori_loop(
            0, steps, tick, (carry, out_buf, stage_bufs))
        # only the last stage holds data; psum over the ring replicates it
        # (other stages contribute zeros) so out_specs=P() is truthful
        return lax.psum(out_buf, axis), stage_bufs

    def pipelined(block_stacked, block_buffers, h_mbs):
        in_specs = (
            jax.tree_util.tree_map(lambda _: P(axis), block_stacked),
            jax.tree_util.tree_map(lambda _: P(axis), block_buffers),
            P(),
        )

        def core_wrap(bs_local, bb_local, h):
            # shard_map hands local views [1, bps, ...]; drop the pp axis
            bs_local = {k: v[0] for k, v in bs_local.items()}
            bb_local = {k: v[0] for k, v in bb_local.items()}
            out, new_bufs = pipeline_core(bs_local, bb_local, h)
            new_bufs = {k: v[None] for k, v in new_bufs.items()}
            return out, new_bufs

        fn = shard_map(
            core_wrap, mesh=mesh,
            in_specs=in_specs,
            out_specs=(P(), jax.tree_util.tree_map(
                lambda _: P(axis), block_buffers)),
            check_vma=False)
        return fn(block_stacked, block_buffers, h_mbs)

    def forward(pre_params, block_stacked, post_params, x_global, key,
                block_buffers=None, pre_buffers=None, post_buffers=None):
        """Pure pipeline forward over the global batch."""
        pre_buffers = pre_buffers or {}
        post_buffers = post_buffers or {}
        block_buffers = block_buffers if block_buffers is not None else {}
        mb = x_global.shape[0] // M
        rng_mod.push_trace_key(key)
        try:
            with autograd.no_grad():
                if pipe_layer.pre is not None:
                    h, _ = functional_call(pipe_layer.pre, pre_params,
                                           pre_buffers, (x_global,),
                                           training=training)
                else:
                    h = x_global
                h_mbs = h.reshape((M, mb) + h.shape[1:])
                out_mbs, new_block_buffers = pipelined(
                    block_stacked, block_buffers, h_mbs)
                out = out_mbs.reshape((M * mb,) + out_mbs.shape[2:])
                if pipe_layer.post is not None:
                    out, _ = functional_call(pipe_layer.post, post_params,
                                             post_buffers, (out,),
                                             training=training)
        finally:
            rng_mod.pop_trace_key()
        return out, new_block_buffers

    return forward, pnames, bnames


# ===========================================================================
# 1F1B — hand-scheduled per-tick VJPs, live activations O(P) not O(M)
# ===========================================================================

def build_pipeline_1f1b_fn(pipe_layer, num_microbatches, loss_fn,
                           mesh=None, training=True, axis="pp"):
    """Returns (step, pnames, bnames) where ``step(pre_params,
    block_stacked, post_params, block_buffers, x_global, labels, key)
    -> (loss, g_pre, g_block, g_post, new_block_buffers)``.

    Loss aggregation: per-microbatch losses are averaged (sum / M),
    which equals GPipe's full-batch loss for MEAN-reduced criteria (the
    framework's standard losses).  A reduction='sum' criterion differs
    by a factor of M between schedules — use GPipe for sum-reduced
    losses.

    Schedule (synchronous lockstep; one ppermute forward + one reverse
    per tick): stage ``s`` runs the FORWARD of microbatch ``m`` at tick
    ``t = m + s`` and the BACKWARD of ``m`` at ``t = (2P - 1 - s) + m``;
    the last stage's backward seeds from the per-microbatch head+loss
    VJP one tick after its forward.  In-flight inputs per stage are
    bounded by ``2(P - s) - 1 <= 2P - 1``, stored in a 2P-slot ring
    buffer — live state is O(P) instead of GPipe's O(M).  Backward
    recomputes the stage forward from the stored INPUT inside its VJP
    (per-tick rematerialization), so residuals never accumulate.
    """
    mesh = mesh or mesh_mod.ensure_mesh()
    pp = int(mesh.shape.get(axis, 1))
    template = pipe_layer.blocks[0]
    pnames = [n for n, _ in template.named_parameters()]
    bnames = [n for n, b in template.named_buffers() if b is not None]
    M = int(num_microbatches)
    B = 2 * pp  # input ring-buffer slots; in-flight < 2P proves safety
    T = M + 2 * pp - 2 + 1  # last backward: stage 0, m=M-1 at 2P-2+M-1

    def stage_fwd(sp, sb, x):
        return _run_stage(template, pnames, bnames, sp, sb, x, training)

    _buf_check_done = []

    def _check_recompute_buffer_safety(sp, sb, x_example):
        """The backward recompute replays the stage forward against
        step-start buffers while the forward sub-tick used
        per-microbatch-advanced ones.  Sound ONLY when the training
        forward's ACTIVATION never reads buffer values (it may still
        WRITE running stats — BN does exactly that, normalizing with
        batch stats).  Verified mechanically once per build: DCE the
        stage jaxpr keeping just the activation output and confirm no
        buffer input survives."""
        if _buf_check_done or not bnames or not training:
            return
        from jax.interpreters import partial_eval as pe
        jaxpr = jax.make_jaxpr(
            lambda p, b, x: stage_fwd(p, b, x)[0])(sp, sb, x_example)
        n_p = len(jax.tree_util.tree_leaves(sp))
        n_b = len(jax.tree_util.tree_leaves(sb))
        _, used = pe.dce_jaxpr(jaxpr.jaxpr,
                               [True] * len(jaxpr.jaxpr.outvars))
        buf_used = used[n_p:n_p + n_b]
        if any(buf_used):
            # dict pytrees flatten in sorted-key order
            names = [n for n, u in zip(sorted(sb), buf_used) if u]
            raise NotImplementedError(
                "1F1B: this stage's TRAINING forward reads buffer "
                f"values ({names}); the per-tick recompute would replay "
                "it against step-start buffers and silently diverge "
                "from the actual forward.  Use the GPipe schedule "
                "(which stores no stale snapshots) for buffer-READING "
                "training forwards.")
        # marked done only AFTER passing — a caught-and-retried failing
        # first step must re-run the guard, not skip into unsound math
        _buf_check_done.append(True)

    def head_loss(post_params, out_mb, label_mb):
        with autograd.no_grad():
            if pipe_layer.post is not None:
                out_mb, _ = functional_call(
                    pipe_layer.post, post_params, {}, (out_mb,),
                    training=training)
            from ..core.tensor import Tensor
            if loss_fn is None:
                loss_t = out_mb
            else:
                loss_t = loss_fn(Tensor(out_mb), Tensor(label_mb))
                loss_t = loss_t._data if isinstance(loss_t, Tensor) \
                    else loss_t
        return jnp.asarray(loss_t, jnp.float32)

    def core(stage_params, stage_bufs, post_params, h_mbs, labels_mbs,
             key):
        _check_recompute_buffer_safety(stage_params, stage_bufs, h_mbs[0])
        stage = lax.axis_index(axis)
        n = pp
        mb_shape = h_mbs.shape[1:]
        perm_fwd = [(i, (i + 1) % n) for i in range(n)]
        perm_bwd = [(i, (i - 1) % n) for i in range(n)]
        is_last = stage == n - 1

        def composed(sp, post_p, inp, label, k):
            """loss-and-activation of this stage; the single VJP target.
            Seeding (1, 0) gives the last stage's head+loss backward;
            seeding (0, cot) gives an interior stage's backward."""
            rng_mod.push_trace_key(k)
            try:
                with autograd.no_grad():
                    out, _ = stage_fwd(sp, stage_bufs_frozen, inp)
                    loss = head_loss(post_p, out, label)
            finally:
                rng_mod.pop_trace_key()
            return loss, out

        # buffers are advanced on forward ticks only; the VJP recompute
        # reads a frozen copy (no double-update of running stats)
        stage_bufs_frozen = stage_bufs

        state = dict(
            act_carry=jnp.zeros(mb_shape, h_mbs.dtype),
            cot_carry=jnp.zeros(mb_shape, h_mbs.dtype),
            in_buf=jnp.zeros((B,) + mb_shape, h_mbs.dtype),
            dh_buf=jnp.zeros((M,) + mb_shape, h_mbs.dtype),
            g_stage={k: jnp.zeros_like(v) for k, v in
                     stage_params.items()},
            g_post=jax.tree_util.tree_map(jnp.zeros_like, post_params),
            loss_acc=jnp.zeros((), jnp.float32),
            bufs=stage_bufs,
        )

        def tick(t, st):
            # ---- forward sub-tick: stage s, microbatch f_m = t - s ----
            f_m = t - stage
            f_active = jnp.logical_and(f_m >= 0, f_m < M)
            feed = lax.dynamic_index_in_dim(
                h_mbs, jnp.clip(f_m, 0, M - 1), axis=0, keepdims=False)
            inp = jnp.where(stage == 0, feed, st["act_carry"])
            k_f = jax.random.fold_in(jax.random.fold_in(key, stage),
                                     jnp.clip(f_m, 0, M - 1))
            rng_mod.push_trace_key(k_f)
            try:
                with autograd.no_grad():
                    act, new_bufs = stage_fwd(stage_params, st["bufs"],
                                              inp)
            finally:
                rng_mod.pop_trace_key()
            bufs = _tree_where(jnp.logical_and(f_active, training),
                               new_bufs, st["bufs"])
            in_buf = jnp.where(
                f_active,
                lax.dynamic_update_index_in_dim(
                    st["in_buf"], inp, jnp.clip(f_m, 0, M - 1) % B,
                    axis=0),
                st["in_buf"])
            act_send = jnp.where(f_active, act,
                                 jnp.zeros_like(act))
            act_carry = lax.ppermute(act_send, axis, perm_fwd)

            # ---- backward sub-tick: microbatch b_m = t - (2n-1-s) -----
            b_m = t - (2 * n - 1 - stage)
            b_active = jnp.logical_and(b_m >= 0, b_m < M)
            b_idx = jnp.clip(b_m, 0, M - 1)
            stored_inp = lax.dynamic_index_in_dim(
                in_buf, b_idx % B, axis=0, keepdims=False)
            label_mb = lax.dynamic_index_in_dim(
                labels_mbs, b_idx, axis=0, keepdims=False)
            k_b = jax.random.fold_in(jax.random.fold_in(key, stage),
                                     b_idx)
            (loss_m, _), vjp_fn = jax.vjp(
                lambda sp, pp_, i: composed(sp, pp_, i, label_mb, k_b),
                stage_params, post_params, stored_inp)
            seed_loss = jnp.where(is_last, 1.0, 0.0).astype(jnp.float32)
            seed_act = jnp.where(is_last,
                                 jnp.zeros(mb_shape, act.dtype),
                                 st["cot_carry"])
            g_sp, g_pp, g_inp = vjp_fn((seed_loss, seed_act))
            g_stage = jax.tree_util.tree_map(
                lambda acc, g: acc + jnp.where(b_active, g,
                                               jnp.zeros_like(g)),
                st["g_stage"], g_sp)
            g_post = jax.tree_util.tree_map(
                lambda acc, g: acc + jnp.where(b_active, g,
                                               jnp.zeros_like(g)),
                st["g_post"], g_pp)
            loss_acc = st["loss_acc"] + jnp.where(
                jnp.logical_and(b_active, is_last), loss_m, 0.0)
            # stage 0's input cotangent is d loss_m / d h_mb
            dh_buf = jnp.where(
                jnp.logical_and(b_active, stage == 0),
                lax.dynamic_update_index_in_dim(
                    st["dh_buf"], g_inp, b_idx, axis=0),
                st["dh_buf"])
            cot_send = jnp.where(b_active, g_inp,
                                 jnp.zeros_like(g_inp))
            cot_carry = lax.ppermute(cot_send, axis, perm_bwd)

            return dict(act_carry=act_carry, cot_carry=cot_carry,
                        in_buf=in_buf, dh_buf=dh_buf, g_stage=g_stage,
                        g_post=g_post, loss_acc=loss_acc, bufs=bufs)

        st = lax.fori_loop(0, T, tick, state)
        # last stage holds loss + g_post; stage 0 holds dh; psum merges
        # (inactive stages contributed zeros)
        loss = lax.psum(st["loss_acc"], axis)
        g_post = jax.tree_util.tree_map(
            lambda g: lax.psum(g, axis), st["g_post"])
        dh = lax.psum(st["dh_buf"], axis)
        return loss, dh, st["g_stage"], g_post, st["bufs"]

    def step(pre_params, block_stacked, post_params, block_buffers,
             x_global, labels, key):
        block_buffers = block_buffers if block_buffers is not None else {}
        mb = x_global.shape[0] // M

        def pre_fn(pp_):
            with autograd.no_grad():
                rng_mod.push_trace_key(jax.random.fold_in(key, 10 ** 6))
                try:
                    if pipe_layer.pre is not None:
                        h, _ = functional_call(pipe_layer.pre, pp_, {},
                                               (x_global,),
                                               training=training)
                    else:
                        h = x_global
                finally:
                    rng_mod.pop_trace_key()
            return h

        h, pre_vjp = jax.vjp(pre_fn, pre_params)
        h_mbs = h.reshape((M, mb) + h.shape[1:])
        lab_mbs = labels.reshape((M, mb) + labels.shape[1:]) \
            if labels is not None else jnp.zeros((M, mb), jnp.int32)

        in_specs = (
            jax.tree_util.tree_map(lambda _: P(axis), block_stacked),
            jax.tree_util.tree_map(lambda _: P(axis), block_buffers),
            jax.tree_util.tree_map(lambda _: P(), post_params),
            P(), P(), P(),
        )
        out_specs = (
            P(),                                             # loss
            P(),                                             # dh
            jax.tree_util.tree_map(lambda _: P(axis), block_stacked),
            jax.tree_util.tree_map(lambda _: P(), post_params),
            jax.tree_util.tree_map(lambda _: P(axis), block_buffers),
        )

        def core_wrap(bs_local, bb_local, post_p, h_m, lab_m, k):
            bs_local = {k2: v[0] for k2, v in bs_local.items()}
            bb_local = {k2: v[0] for k2, v in bb_local.items()}
            loss, dh, g_stage, g_post, bufs = core(
                bs_local, bb_local, post_p, h_m, lab_m, k)
            return (loss, dh,
                    {k2: v[None] for k2, v in g_stage.items()},
                    g_post,
                    {k2: v[None] for k2, v in bufs.items()})

        fn = shard_map(core_wrap, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
        loss, dh, g_block, g_post, new_bufs = fn(
            block_stacked, block_buffers, post_params, h_mbs, lab_mbs,
            key)
        dh_flat = dh.reshape((M * mb,) + dh.shape[2:])
        (g_pre,) = pre_vjp(dh_flat.astype(h.dtype))
        scale = 1.0 / M
        loss = loss * scale
        g_pre = jax.tree_util.tree_map(lambda g: g * scale, g_pre)
        g_block = jax.tree_util.tree_map(lambda g: g * scale, g_block)
        g_post = jax.tree_util.tree_map(lambda g: g * scale, g_post)
        return loss, g_pre, g_block, g_post, new_bufs

    return step, pnames, bnames
