"""Plain reference for the block-diffusion routed-experts decoder (the
SDAR-MoE family: SDAR-30B-A3B-Chat): the forward pass and the published
generation loop in straightforward ``jax.numpy``, float32 with matrix
products at ``highest`` precision.  Keys and values repeated over their
query heads, a loop over experts in which every expert sees every row,
no cache, no paging, no kernels, one request at a time; it imports
nothing of the program.

Architecture as the family's published code computes it
(``modeling_sdar_moe``: Qwen3-MoE's layer under a block-causal mask),
with the departures the configuration's file lists under ``assumed``:

* pre-norm blocks ``x += attn(rms(x)); x += moe(rms(x))``, RMSNorm with
  epsilon ``rms_norm_eps``, a final norm and an untied head; no bias;
* attention: ``q = h W_q`` [H, hd], ``k = h W_k``, ``v = h W_v``
  [K, hd]; each head of q and k RMS-normed over hd with a learned gain,
  then rotated (pairs (i, i + hd/2), angle ``pos * theta^(-2i/hd)``);
  query head i reads K/V head ``i // (H / K)``; scores ``/ sqrt(hd)``;
  position i sees j iff ``j // B <= i // B`` (B = ``block_length``,
  blocks aligned to absolute positions);
* every layer routed: ``p = softmax(h W_g)`` over all experts in
  float32, the top ``num_experts_per_tok``, their probabilities
  renormalised to sum to one, SwiGLU experts with gate | up side by
  side in one leaf; no shared expert;
* the logits of position i are for the token AT position i;
* generation (``block_diffusion_generate``, ``low_confidence_static``):
  ``generate`` below.  DEPARTURE: whether a position is masked is kept
  as a flag, not read off ``id == mask_token_id`` (the benchmark's
  prompts draw ids over the whole vocabulary).

``dims`` is the configuration's ``dims``: the published keys, under
``generation`` ``block_length``, ``denoising_steps`` and
``mask_token_id``, and under ``seeded`` what the drawn leaves are scaled
by before anyone uses them (``q_norm_scale``: the weights are random, and
the configuration's ``assumed`` says why their attention has to be made
as peaked as a trained model's).

``precision``: ``"highest"`` is the reference; ``"fp8"`` rounds both
operands of every matrix product to float8_e4m3 under a per-tensor
scale; ``"bf16"`` rounds them to bfloat16.  The router's scores stay
float32 in all three.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512        # queries a step of the clean pass's attention loop
HEAD_BLOCK = 256     # rows a step of the head loop
STATE_BLOCK = 32     # block states a step of their attention loop
STATE_CHUNK = 1024   # block states a call of their layer program (one
#                      compiled shape a padded sequence length)


def _divisor(n, cap):
    return next(b for b in range(min(cap, n), 0, -1) if n % b == 0)


def _round(a, precision):
    if precision == "fp8":
        s = 448.0 / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
        return (a * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    if precision == "bf16":
        return a.astype(jnp.bfloat16).astype(jnp.float32)
    if precision != "highest":
        raise ValueError(f"unknown precision {precision!r}")
    return a


def _mm(a, b, precision):
    return jnp.matmul(_round(a, precision), _round(b, precision),
                      precision=jax.lax.Precision.HIGHEST)


def _ein(form, a, b, precision):
    return jnp.einsum(form, _round(a, precision), _round(b, precision),
                      precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x [..., heads, d] at positions ``pos`` [...]."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[..., None, None] * freq
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def q_norm_scale(dims):
    """What the seeded ``attn.q_norm.weight`` leaves are multiplied by
    (``dims["seeded"]``; 1 where the configuration says nothing)."""
    return float(dims.get("seeded", {}).get("q_norm_scale", 1.0))


def _qkv(w, h, pos, dims, precision):
    """h [..., D], pos [...] -> q [..., H, hd], k and v [..., H, hd]:
    K and V repeated over the query heads that read them."""
    H, K = dims["num_attention_heads"], dims["num_key_value_heads"]
    hd, eps = dims["head_dim"], dims["rms_norm_eps"]
    theta = float(dims["rope_theta"])
    lead = h.shape[:-1]
    q = _mm(h, w["attn.q_proj.weight"], precision).reshape(*lead, H, hd)
    k = _mm(h, w["attn.k_proj.weight"], precision).reshape(*lead, K, hd)
    v = _mm(h, w["attn.v_proj.weight"], precision).reshape(*lead, K, hd)
    # the seeded q gains are the drawn leaf times ``seeded.q_norm_scale``
    # (a power of two, so exact in every dtype), as ``build`` scales them
    gain = w["attn.q_norm.weight"] * q_norm_scale(dims)
    q = _rope(_rms(q, gain, eps), pos, theta)
    k = _rope(_rms(k, w["attn.k_norm.weight"], eps), pos, theta)
    return (q, jnp.repeat(k, H // K, axis=-2),
            jnp.repeat(v, H // K, axis=-2))


def moe(w, h, dims, precision):
    """The routed layer over rows h [R, D]: every expert over every
    row, weighted by the gate (0 where not selected)."""
    k = dims["num_experts_per_tok"]
    p = jax.nn.softmax(jnp.matmul(h, w["ffn.gate_weight"],
                                  precision=jax.lax.Precision.HIGHEST), -1)
    picked, chosen = jax.lax.top_k(p, k)
    if dims.get("norm_topk_prob", True):
        picked = picked / picked.sum(-1, keepdims=True)
    gate = jnp.zeros_like(p).at[
        jnp.arange(h.shape[0])[:, None], chosen].set(picked)     # [R, E]
    f = w["ffn.experts_out"].shape[1]

    def one_expert(y, e):
        a = _mm(h, w["ffn.experts_in"][e], precision)
        out = _mm(jax.nn.silu(a[:, :f]) * a[:, f:],
                  w["ffn.experts_out"][e], precision)
        return y + gate[:, e][:, None] * out, None
    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                        jnp.arange(p.shape[-1]))
    return y


def clean_layer(w, x, dims, precision):
    """One layer over a whole sequence x [T, D] at positions 0..T-1
    under the block-causal mask.  Returns (x, k [T, H, hd], v)."""
    T, D = x.shape
    B = dims["generation"]["block_length"]
    eps, hd = dims["rms_norm_eps"], dims["head_dim"]
    h = _rms(x, w["input_norm.weight"], eps)
    q, k, v = _qkv(w, h, jnp.arange(T), dims, precision)
    qb = _divisor(T, Q_BLOCK)

    def some_queries(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
        s = _ein("qhd,khd->hqk", qs, k, precision) / math.sqrt(hd)
        at = i * qb + jnp.arange(qb)
        s = jnp.where((jnp.arange(T)[None, :] // B
                       <= at[:, None] // B)[None], s, -jnp.inf)
        return _ein("hqk,khd->qhd", jax.nn.softmax(s, -1), v, precision)
    o = jax.lax.map(some_queries, jnp.arange(T // qb)).reshape(T, -1)
    x = x + _mm(o, w["attn.o_proj.weight"], precision)
    x = x + moe(w, _rms(x, w["post_norm.weight"], eps), dims, precision)
    return x, k, v


def state_layer(w, xs, start, k_clean, v_clean, dims, precision):
    """One layer over block states xs [N, B, D]: state n stands at
    positions ``start[n] ..`` and sees the clean sequence's rows below
    ``start[n]`` (``k_clean`` / ``v_clean`` [T, H, hd]) and its own B
    rows, all of them."""
    N, B, D = xs.shape
    T = k_clean.shape[0]
    eps, hd = dims["rms_norm_eps"], dims["head_dim"]
    h = _rms(xs, w["input_norm.weight"], eps)
    q, k, v = _qkv(w, h, start[:, None] + jnp.arange(B)[None, :], dims,
                   precision)
    nb = _divisor(N, STATE_BLOCK)

    def some_states(i):
        def cut(a):
            return jax.lax.dynamic_slice_in_dim(a, i * nb, nb)
        qs, ks, vs, st = cut(q), cut(k), cut(v), cut(start)
        s_old = _ein("nqhd,khd->nhqk", qs, k_clean, precision)
        s_old = jnp.where((jnp.arange(T)[None, :] < st[:, None])
                          [:, None, None, :], s_old, -jnp.inf)
        s_own = _ein("nqhd,nkhd->nhqk", qs, ks, precision)
        p = jax.nn.softmax(
            jnp.concatenate([s_old, s_own], -1) / math.sqrt(hd), -1)
        return (_ein("nhqk,khd->nqhd", p[..., :T], v_clean, precision)
                + _ein("nhqk,nkhd->nqhd", p[..., T:], vs, precision))
    o = jax.lax.map(some_states, jnp.arange(N // nb)).reshape(N, B, -1)
    xs = xs + _mm(o, w["attn.o_proj.weight"], precision)
    y = moe(w, _rms(xs, w["post_norm.weight"], eps).reshape(N * B, D),
            dims, precision)
    return xs + y.reshape(N, B, D)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _frozen(dims):
    def freeze(v):
        if isinstance(v, dict):
            return tuple(sorted((k, freeze(x)) for k, x in v.items()))
        return tuple(v) if isinstance(v, list) else v
    return freeze(dims)


def _thaw(frozen):
    return {k: (dict(v) if isinstance(v, tuple) and v
                and isinstance(v[0], tuple) else v) for k, v in frozen}


@functools.partial(jax.jit, static_argnums=(2, 3))
def _clean_jit(w, x, dims, precision):
    return clean_layer(_f32(w), x, _thaw(dims), precision)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _state_jit(w, xs, start, k_clean, v_clean, dims, precision):
    return state_layer(_f32(w), xs, start, k_clean, v_clean, _thaw(dims),
                       precision)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(w, x, want, eps, precision):
    """Over rows x [R, D]: the best logit less the logit of ``want``
    [R], the best token, and log c, c the best token's probability;
    ``HEAD_BLOCK`` rows at a time."""
    w = _f32(w)
    R, D = x.shape
    hb = _divisor(R, HEAD_BLOCK)

    def some(args):
        xs, wt = args
        logits = _mm(_rms(xs, w["norm.weight"], eps), w["lm_head.weight"],
                     precision)
        best = logits.max(-1)
        got = jnp.take_along_axis(logits, wt[:, None], -1)[:, 0]
        log_c = -jnp.log(jnp.sum(jnp.exp(logits - best[:, None]), -1))
        return best - got, logits.argmax(-1), log_c
    out = jax.lax.map(some, (x.reshape(R // hb, hb, D),
                             want.reshape(R // hb, hb)))
    return tuple(a.reshape(R) for a in out)


LAYER_LEAVES = ("input_norm.weight", "attn.q_proj.weight",
                "attn.k_proj.weight", "attn.v_proj.weight",
                "attn.q_norm.weight", "attn.k_norm.weight",
                "attn.o_proj.weight", "post_norm.weight",
                "ffn.gate_weight", "ffn.experts_in", "ffn.experts_out")


def _layers(get_weights, dims):
    """Each layer's leaves in turn, made once and dropped."""
    for i in range(dims["num_hidden_layers"]):
        pre = f"blocks.{i}."
        w = get_weights(tuple(pre + n for n in LAYER_LEAVES))
        yield {n: w[pre + n] for n in LAYER_LEAVES}


def logits(get_weights, dims, ids, masked=None, precision="highest"):
    """[T, V] logits of ONE whole sequence under the block-causal mask;
    ``masked`` [T] bool puts the mask token there (small sizes: the
    tests and ``generate``)."""
    ids = jnp.asarray(ids, jnp.int32)
    if masked is not None:
        ids = jnp.where(jnp.asarray(masked),
                        dims["generation"]["mask_token_id"], ids)
    x = _f32(get_weights(("embed",)))["embed"][ids]
    for w in _layers(get_weights, dims):
        x, _, _ = _clean_jit(w, x, _frozen(dims), precision)
    w = _f32(get_weights(("norm.weight", "lm_head.weight")))
    return _mm(_rms(x, w["norm.weight"], dims["rms_norm_eps"]),
               w["lm_head.weight"], precision)


forward = logits


def generate(get_weights, dims, prompt, n, eos=None, order=None):
    """The published loop, greedy, static schedule: ``n`` tokens after
    ``prompt`` (fewer after an ``eos``).  Every pass runs the whole
    sequence so far without a cache (earlier blocks' rows do not depend
    on later ones under the mask, so that is what a cache would hold).
    A block: the prompt's tail (first block only) then masks; each pass
    fixes the ``B / T`` masked positions of highest ``c =
    softmax(logits)[x0]`` to ``x0 = argmax``; with none left the block
    is final and the next one opens.  ``order`` (tests): a function
    ``(masked positions, c) -> positions to fix`` in place of the
    confidence rule.  Returns the answer's tokens in position order."""
    gen = dims["generation"]
    B, per_pass = gen["block_length"], \
        gen["block_length"] // gen["denoising_steps"]
    seq = [int(t) for t in prompt]
    n0, total = len(seq), len(seq) + n
    while len(seq) < total:
        start = len(seq) // B * B
        ids = np.asarray(seq[:start] + (seq[start:] + [0] * B)[:B])
        masked = np.zeros(len(ids), bool)
        masked[len(seq):] = True
        while masked.any():
            lg = np.asarray(logits(get_weights, dims, ids, masked))
            x0 = lg.argmax(-1)
            c = np.exp(lg.max(-1) - jax.nn.logsumexp(lg, -1))
            open_ = np.flatnonzero(masked)
            if order is not None:
                fix = order(open_, c)
            else:
                fix = open_[np.argsort(-c[open_], kind="stable")
                            ][:per_pass]
            ids[fix] = x0[fix]
            masked[fix] = False
        seq = [int(t) for t in ids]
    out = seq[n0:total]
    if eos is not None and eos in out:
        out = out[:out.index(eos) + 1]
    return out


# -- the comparison that decides ``correct`` ------------------------------

def _block_states(n, end, B):
    """The states of every answer block whose B tokens are all known
    (positions ``< end``): for block k (positions ``kB ..``) and every
    PROPER subset S of its served positions (those ``>= n``), the state
    in which S and the prompt's tail are filled and the rest masked.
    Returns [(k, filled bitmask over the block's rows)], and per block
    the bitmask of its served rows."""
    states, served_rows = [], {}
    for k in range(n // B, end // B):
        rows = [j for j in range(B) if k * B + j >= n]
        given = sum(1 << j for j in range(B) if k * B + j < n)
        served_rows[k] = sum(1 << j for j in rows)
        for sub in range(1 << len(rows)):
            if sub == (1 << len(rows)) - 1:
                continue
            filled = given + sum(1 << rows[i] for i in range(len(rows))
                                 if sub >> i & 1)
            states.append((k, filled))
    return states, served_rows


def _cheapest_chains(states, served_rows, r, o, B):
    """For every block the cheapest chain of steps from "nothing
    served is filled" to "all of it is", a step from state S fixing its
    masked row p costing ``r_S(p) + o_S(p)``.  r, o [N, B] follow
    ``states``.  Returns {block: {row: (cost, state index)}}: what each
    row is charged and at which state it was fixed."""
    by_block = {}
    for i, (k, filled) in enumerate(states):
        by_block.setdefault(k, {})[filled] = i
    out = {}
    for k, of in by_block.items():
        full = max(of) | served_rows[k]
        best = {min(of): (0.0, {})}          # filled -> (cost, charges)
        for filled in sorted(of, key=lambda f: bin(f).count("1")):
            cost, charges = best[filled]
            i = of[filled]
            for p in range(B):
                if filled >> p & 1:
                    continue
                step = float(r[i, p] + o[i, p])
                nxt = filled | 1 << p
                if nxt not in best or cost + step < best[nxt][0]:
                    best[nxt] = (cost + step, {**charges, p: (step, i)})
        out[k] = best[full][1]
    return out


def served_regret(get_weights, dims, ids, served, precision="highest",
                  rows_per_block=1):
    """Regret of served tokens, whatever order their blocks' positions
    were fixed in.

    ``get_weights(names)`` returns the named leaves (any float type);
    ``ids`` [R, T] are prompt + served tokens, right-padded; ``served``
    [R, T] is -1 except that index t holds the token served AT position
    t + 1 (the harness's convention for a model whose logits are of
    the NEXT token; here the logits of a position are of its own token,
    so it is shifted back).  ``rows_per_block`` is the harness's
    batching of requests: this reference takes one at a time.

    The harness gives prompt and served tokens and nothing of the order
    in which a block's positions were fixed, and two precisions will
    not agree on that order.  So every order is followed: one clean
    pass over prompt + answer gives each layer's K/V; for every answer
    block whose tokens are all known (the answer's last, partly sent
    block is left out of ``valid``) and every proper subset S of its
    served positions, the block with S filled and the rest masked runs
    against the clean K/V of the earlier blocks.  For a step from S
    that fixes p: ``r_S(p)`` = the best logit at p less the served
    token's, ``o_S(p)`` = log of the highest c among the masked less
    log c at p.  A block's served tokens are charged the CHEAPEST
    chain of steps from the empty set to the full one, each token ``r
    + o`` at the step that fixed it: a sound program's own chain is
    cheap, so the least is; a program that fixes the wrong position,
    picks from a stale state or reads a stale K/V row is dear along
    every chain.  Returns (regret [R, T], valid [R, T], argmax [R, T])
    as numpy, indexed as ``served`` is; argmax is the best token at the
    state the chain fixed the position in."""
    ids = np.asarray(ids, np.int32)
    served = np.asarray(served, np.int32)
    gen = dims["generation"]
    B, mask_id = gen["block_length"], gen["mask_token_id"]
    R, T = ids.shape
    frozen = _frozen(dims)
    regret = np.zeros((R, T), np.float32)
    valid = np.zeros((R, T), bool)
    top = np.zeros((R, T), np.int32)
    emb = get_weights(("embed",))["embed"]
    work = []
    for b in range(R):
        at = np.flatnonzero(served[b] >= 0)
        if not len(at):
            continue
        n, end = int(at[0]) + 1, int(at[-1]) + 2
        states, served_rows = _block_states(n, end, B)
        if not states:
            continue
        N = -(-len(states) // STATE_CHUNK) * STATE_CHUNK
        start = np.zeros(N, np.int32)
        tok = np.zeros((N, B), np.int32)
        for i, (k, filled) in enumerate(states):
            start[i] = k * B
            blk = ids[b, k * B:(k + 1) * B]
            tok[i] = [blk[j] if filled >> j & 1 else mask_id
                      for j in range(B)]
        want = np.zeros((N, B), np.int32)
        for i, (k, _) in enumerate(states):
            want[i] = ids[b, k * B:(k + 1) * B]
        work.append(dict(
            b=b, states=states, served_rows=served_rows,
            start=jnp.asarray(start), want=jnp.asarray(want),
            x=emb[jnp.asarray(ids[b])].astype(jnp.float32),
            xs=emb[jnp.asarray(tok)].astype(jnp.float32)))
    del emb
    for w in _layers(get_weights, dims):
        for it in work:
            it["x"], k, v = _clean_jit(w, it["x"], frozen, precision)
            it["xs"] = jnp.concatenate([
                _state_jit(w, it["xs"][lo:lo + STATE_CHUNK],
                           it["start"][lo:lo + STATE_CHUNK], k, v, frozen,
                           precision)
                for lo in range(0, it["xs"].shape[0], STATE_CHUNK)])
        del w
    w = get_weights(("norm.weight", "lm_head.weight"))
    for it in work:
        b, states = it["b"], it["states"]
        N = it["xs"].shape[0]
        r, best, log_c = (np.concatenate(parts).reshape(N, B)
                          for parts in zip(*[
            [np.asarray(a) for a in _head(
                w, it["xs"][lo:lo + STATE_CHUNK].reshape(
                    STATE_CHUNK * B, -1),
                it["want"][lo:lo + STATE_CHUNK].reshape(STATE_CHUNK * B),
                float(dims["rms_norm_eps"]), precision)]
            for lo in range(0, N, STATE_CHUNK)]))
        o = np.zeros((N, B), np.float32)
        for i, (_, filled) in enumerate(states):
            open_ = [p for p in range(B) if not filled >> p & 1]
            o[i, open_] = log_c[i, open_].max() - log_c[i, open_]
        for k, charges in _cheapest_chains(
                states, it["served_rows"], r, o, B).items():
            for p, (cost, i) in charges.items():
                t = k * B + p - 1                # ``served``'s index
                regret[b, t], valid[b, t] = cost, True
                top[b, t] = best[i, p]
    return regret, valid, top
