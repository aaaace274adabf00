"""Ring attention — sequence/context parallelism over an ICI mesh axis.

NEW capability relative to the reference (SURVEY.md §5.7: absent there).
Design: blockwise attention with online softmax; K/V blocks rotate around
the 'sp' ring via ``lax.ppermute`` while each device keeps its Q shard, so
peak memory is O(S_local²) and the sequence scales with the ring size.
Causal masking uses the ring step to decide block visibility.  The ring
loop is a ``lax.scan``, so the whole kernel is reverse-mode
differentiable — sequence-parallel TRAINING works through plain
``jax.grad`` (the scan transpose rotates cotangents on the reverse ring).

Layout convention (paddle): [batch, seq, heads, head_dim]; the seq axis is
sharded over `axis`.
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ..core.tensor import Tensor
from ..core.dispatch import ensure_tensor
from . import mesh as mesh_mod


def _block_attn(q, k, v, scale, mask_mode, drop_key=None, dropout_p=0.0):
    """One block pair: returns (unnormalized out, running max, running sum)
    contributions in f32.  mask_mode: 0=full, 1=causal-diag, 2=skip.

    Attention dropout composes with the online softmax: the mask applies
    only to the ``o`` accumulation (probs→dropout→@v), while ``m``/``l``
    stay undropped — (p·mask/(1-pd)) @ v / l == dropout(softmax(s)) @ v.
    """
    # q,k,v: [B, S, H, D] -> scores [B, H, Sq, Sk]
    qh = jnp.swapaxes(q, 1, 2).astype(jnp.float32)
    kh = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vh = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * scale
    if mask_mode == 1:
        sq, sk = s.shape[-2], s.shape[-1]
        # sk - sq offset aligns the diagonal when query/key lengths
        # differ (decode-style calls); identical to _reference_attention.
        # Ring blocks always have sq == sk, where this is plain tril.
        causal = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(causal, s, -jnp.inf)
    m = jnp.max(s, axis=-1)                     # [B,H,Sq]
    m = jnp.maximum(m, -1e30)                   # avoid -inf - -inf
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)                     # [B,H,Sq]
    if drop_key is not None and dropout_p > 0.0:
        keep = jax.random.bernoulli(drop_key, 1.0 - dropout_p, p.shape)
        p = p * keep / (1.0 - dropout_p)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vh)
    return o, m, l


def _single_block_attention(q, k, v, scale, causal, drop_key, dropout_p):
    """Full (non-ring) attention with probs-dropout in [B, S, H, D]
    layout — the degenerate-ring and Ulysses-local code path."""
    o, _, l = _block_attn(q, k, v,
                          scale if scale is not None else
                          1.0 / math.sqrt(q.shape[-1]),
                          mask_mode=1 if causal else 0,
                          drop_key=drop_key, dropout_p=dropout_p)
    out = o / jnp.maximum(l[..., None], 1e-30)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


# Bounded LRU of jitted shard_map calls.  The compiled fn closes over the
# Mesh (shard_map), so weak keying cannot work — instead cap the entry
# count; eviction drops the executable AND its mesh reference together.
from collections import OrderedDict

_RING_CACHE_CAP = 16
_ring_jit_cache: "OrderedDict" = OrderedDict()


def _get_placeholder_key():
    # NEVER cached: the first call can happen inside a jit trace, and a
    # module-global would then hold that trace's tracer — leaking it
    # into every later trace (UnexpectedTracerError; found by the slow
    # lane's test ordering).  Creation is microseconds.
    return jax.random.key(0)


def _mesh_cache_key(mesh):
    """Value-based mesh identity: axis names/sizes + device ids.  Keying
    on id(mesh) would let a recreated mesh at a recycled address alias a
    stale compiled entry."""
    return (tuple(mesh.shape.items()),
            tuple(d.id for d in mesh.devices.flat))


def _cached_sp_call(mesh, subkey, build):
    key = (_mesh_cache_key(mesh), subkey)
    if key in _ring_jit_cache:
        _ring_jit_cache.move_to_end(key)
        return _ring_jit_cache[key][1]
    fn = build()
    _ring_jit_cache[key] = (mesh, fn)  # keep mesh alive while cached
    while len(_ring_jit_cache) > _RING_CACHE_CAP:
        _ring_jit_cache.popitem(last=False)
    return fn


def _localize_eager(out, ref):
    """Eager results leave the shard_map mesh-sharded; surrounding eager
    code (residual adds, numpy()) works on single-device arrays — pull
    the result back to the reference operand's device."""
    if isinstance(ref, jax.core.Tracer) or not isinstance(out, jax.Array):
        return out
    devs = getattr(ref, "devices", lambda: set())()
    if len(devs) == 1 and len(out.devices()) > 1:
        # on-device gather (no host round-trip)
        return jax.device_put(out, next(iter(devs)))
    return out


def _sp_place_and_spec(mesh, axis, q, k, v, claim_mp_heads):
    """Shared placement logic for the sequence-parallel drivers:
    tracer-aware specs (keep surrounding batch/mp shardings under pjit,
    only when the dims divide) + explicit mesh placement of concrete
    operands mixed into a traced call."""
    if not isinstance(q, jax.core.Tracer):
        spec = P(None, axis, None, None)
        sharding = jax.sharding.NamedSharding(mesh, spec)
        q, k, v = (jax.device_put(a, sharding) for a in (q, k, v))
        return spec, q, k, v
    batch_axes = tuple(a for a in mesh_mod.DATA_AXES
                       if mesh.shape.get(a, 1) > 1)
    bsz = int(np.prod([mesh.shape[a] for a in batch_axes])) \
        if batch_axes else 1
    if not batch_axes or q.shape[0] % bsz != 0:
        batch_axes = None
    mp_n = mesh.shape.get("mp", 1)
    head_ax = "mp" if (claim_mp_heads and mp_n > 1
                       and q.shape[2] % mp_n == 0) else None
    spec = P(batch_axes, axis, head_ax, None)
    sharding = jax.sharding.NamedSharding(mesh, spec)
    q, k, v = (a if isinstance(a, jax.core.Tracer)
               else jax.device_put(np.asarray(a), sharding)
               for a in (q, k, v))
    return spec, q, k, v


def _ring_attention_local(q, k, v, axis, causal, scale, key=None,
                          dropout_p=0.0, fold_axes=()):
    """Runs on each device inside shard_map; q/k/v are LOCAL seq shards."""
    n = lax.axis_size(axis)
    my = lax.axis_index(axis)
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])

    b, sq, h, d = q.shape
    acc_o = jnp.zeros((b, h, sq, d), jnp.float32)
    acc_m = jnp.full((b, h, sq), -1e30, jnp.float32)
    acc_l = jnp.zeros((b, h, sq), jnp.float32)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(carry, step):
        # lax.scan (NOT fori_loop): scan is reverse-mode differentiable,
        # so ring attention TRAINS through plain jax.grad — the backward
        # is the transposed scan with reverse ppermutes (fori_loop lowers
        # to while_loop, which has no reverse rule)
        k_blk, v_blk, acc_o, acc_m, acc_l = carry
        # k_blk originated on device (my - step) mod n
        src = (my - step) % n
        # per-(device, ring-step) dropout key: deterministic fold, so the
        # scan transpose (backward) regenerates the identical mask
        dkey = None
        if key is not None and dropout_p > 0.0:
            dkey = jax.random.fold_in(jax.random.fold_in(key, my), step)
            # decorrelate across the OTHER mesh axes (dp/sharding/mp):
            # replicas holding different data/head shards must not share
            # a mask
            for fa in fold_axes:
                dkey = jax.random.fold_in(dkey, lax.axis_index(fa))
        if causal:
            # visible iff src block is strictly earlier, or same (diag).
            # compute full + diag variants and select — cheaper than
            # lax.switch under vjp (both run anyway in backward) and
            # keeps every branch differentiable
            o_f, m_f, l_f = _block_attn(q, k_blk, v_blk, scale,
                                        mask_mode=0, drop_key=dkey,
                                        dropout_p=dropout_p)
            o_d, m_d, l_d = _block_attn(q, k_blk, v_blk, scale,
                                        mask_mode=1, drop_key=dkey,
                                        dropout_p=dropout_p)
            zero_o = jnp.zeros_like(o_f)
            skip_m = jnp.full_like(m_f, -1e30)
            zero_l = jnp.zeros_like(l_f)
            is_full = (src < my)
            is_diag = (src == my)
            o = jnp.where(is_full, o_f, jnp.where(is_diag, o_d, zero_o))
            m = jnp.where(is_full, m_f, jnp.where(is_diag, m_d, skip_m))
            l = jnp.where(is_full, l_f, jnp.where(is_diag, l_d, zero_l))
        else:
            o, m, l = _block_attn(q, k_blk, v_blk, scale, mask_mode=0,
                                  drop_key=dkey, dropout_p=dropout_p)

        new_m = jnp.maximum(acc_m, m)
        alpha = jnp.exp(acc_m - new_m)
        beta = jnp.exp(m - new_m)
        new_l = acc_l * alpha + l * beta
        new_o = acc_o * alpha[..., None] + o * beta[..., None]
        k_next = lax.ppermute(k_blk, axis, perm)
        v_next = lax.ppermute(v_blk, axis, perm)
        return (k_next, v_next, new_o, new_m, new_l), None

    carry = (k, v, acc_o, acc_m, acc_l)
    carry, _ = lax.scan(body, carry, jnp.arange(n))
    _, _, acc_o, _, acc_l = carry
    out = acc_o / jnp.maximum(acc_l[..., None], 1e-30)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)  # [B, S, H, D]


def ring_attention_inner(q, k, v, axis="sp", causal=False, scale=None):
    """For use INSIDE an existing shard_map region (arrays, not Tensors)."""
    return _ring_attention_local(q, k, v, axis, causal, scale)


def ring_attention(query, key, value, axis="sp", causal=False, scale=None,
                   mesh=None, dropout_p=0.0, rng_key=None):
    """Driver: shards the seq axis of global [B, S, H, D] tensors over
    `axis` and runs ring attention.  Usable eagerly or under jit.
    ``dropout_p``/``rng_key``: attention-probability dropout, applied
    per ring block with deterministic per-(device, step) keys."""
    q = ensure_tensor(query)._data
    k = ensure_tensor(key)._data
    v = ensure_tensor(value)._data
    mesh = mesh or mesh_mod.ensure_mesh()
    if mesh.shape.get(axis, 1) == 1:
        # degenerate ring (one block): single-block attention with
        # probs-dropout — the same math the ring applies per block
        if dropout_p > 0.0 and rng_key is not None:
            return Tensor(_single_block_attention(
                q, k, v, scale, causal, rng_key, dropout_p))
        from ..nn.functional.attention import _reference_attention
        return Tensor(_reference_attention(q, k, v, None, scale, causal))

    orig = q
    spec, q, k, v = _sp_place_and_spec(mesh, axis, q, k, v,
                                       claim_mp_heads=True)
    use_drop = dropout_p > 0.0 and rng_key is not None
    if not use_drop:
        rng_key = _get_placeholder_key()  # ignored by the kernel

    def build():
        fold_axes = tuple(a for a in mesh.shape
                          if mesh.shape[a] > 1 and a != axis)

        def local(qq, kk, vv, rk):
            return _ring_attention_local(
                qq, kk, vv, axis=axis, causal=causal, scale=scale,
                key=rk if use_drop else None,
                dropout_p=dropout_p if use_drop else 0.0,
                fold_axes=fold_axes if use_drop else ())

        fn = shard_map(local, mesh=mesh,
                       in_specs=(spec, spec, spec, P()),
                       out_specs=spec, check_vma=False)
        return jax.jit(fn)

    # jit wrapper (cached by config: jit's own cache keys on function
    # identity, so a fresh wrapper per call would recompile the ring
    # kernel every invocation); it also places single-device/host
    # operands onto the mesh.  Under an outer pjit this inlines.
    call = _cached_sp_call(mesh, ("ring", axis, bool(causal), scale,
                                  spec, use_drop,
                                  dropout_p if use_drop else 0.0), build)
    return Tensor(_localize_eager(call(q, k, v, rng_key), orig))


def ulysses_attention(query, key, value, axis="sp", causal=False,
                      scale=None, mesh=None, dropout_p=0.0, rng_key=None):
    """DeepSpeed-Ulysses style context parallelism: all_to_all swaps the
    sharded axis from sequence to heads, runs full-sequence attention on
    1/N of the heads, then swaps back.  Lower comm volume than ring when
    heads % N == 0.  NEW capability (absent in reference).

    ``dropout_p``/``rng_key``: attention-probability dropout applied in
    the LOCAL attention after the all-to-all — each device drops its own
    head shard with a key folded over its mesh coordinates (this axis
    plus every other >1 axis), so no two shards share a mask and the
    global pattern matches single-device semantics (independent
    Bernoulli per (b, h, q, k))."""
    q = ensure_tensor(query)._data
    k = ensure_tensor(key)._data
    v = ensure_tensor(value)._data
    mesh = mesh or mesh_mod.ensure_mesh()
    n = mesh.shape.get(axis, 1)
    use_drop = dropout_p > 0.0 and rng_key is not None
    if n == 1:
        if use_drop:
            # same probs-dropout math the sharded path applies locally
            return Tensor(_single_block_attention(
                q, k, v, scale, causal, rng_key, dropout_p))
        from ..nn.functional.attention import _reference_attention
        return Tensor(_reference_attention(q, k, v, None, scale, causal))

    from ..nn.functional.attention import _reference_attention

    orig = q
    spec, q, k, v = _sp_place_and_spec(mesh, axis, q, k, v,
                                       claim_mp_heads=True)
    # the all_to_all splits each device's LOCAL head count across the sp
    # ring — guard divisibility here rather than dying in XLA lowering
    local_heads = q.shape[2]
    if spec[2] == "mp":
        local_heads //= mesh.shape.get("mp", 1)
    if local_heads % n != 0:
        raise ValueError(
            f"ulysses_attention: local head count {local_heads} is not "
            f"divisible by the '{axis}' degree {n} — use ring attention "
            "(use_sp=True) for head counts the all-to-all cannot split")
    if not use_drop:
        rng_key = _get_placeholder_key()  # ignored by the kernel

    def build():
        fold_axes = tuple(a for a in mesh.shape
                          if mesh.shape[a] > 1 and a != axis)

        def local(q, k, v, rk):
            # local: [B, S/n, H, D] -> a2a -> [B, S, H/n, D]
            def seq2head(x):
                return lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                      tiled=True)

            def head2seq(x):
                return lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                      tiled=True)

            qg, kg, vg = seq2head(q), seq2head(k), seq2head(v)
            if use_drop:
                dkey = jax.random.fold_in(rk, lax.axis_index(axis))
                for fa in fold_axes:
                    dkey = jax.random.fold_in(dkey, lax.axis_index(fa))
                out = _single_block_attention(qg, kg, vg, scale, causal,
                                              dkey, dropout_p)
            else:
                out = _reference_attention(qg, kg, vg, None, scale, causal)
            return head2seq(out)

        fn = shard_map(local, mesh=mesh,
                       in_specs=(spec, spec, spec, P()),
                       out_specs=spec, check_vma=False)
        return jax.jit(fn)

    call = _cached_sp_call(mesh, ("ulysses", axis, bool(causal), scale,
                                  spec, use_drop,
                                  dropout_p if use_drop else 0.0), build)
    return Tensor(_localize_eager(call(q, k, v, rng_key), orig))
