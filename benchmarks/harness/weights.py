"""Seeded weights, made by the benchmark and never read back from the
program.

The program's model gets these values in one jitted call, in the type it
serves or trains in; the reference makes the same values again from the
seed, leaf by leaf, and upcasts them.  Values follow GPT-2's
initialisation (normal, std 0.02) and also fill what GPT-2 starts at
zero or one (biases, layer-norm gains), so that a dropped bias or gain
shows in the comparison.
"""
from __future__ import annotations

import functools

STD = 0.02


def leaf_specs(dims):
    """[(name, shape, kind)] in a fixed order; ``kind`` is ``normal`` or
    ``gain`` (1 + normal).  Names are the program's parameter names."""
    d, f = dims["hidden_size"], dims["ffn_hidden_size"]
    out = [("embeddings.word_embeddings.weight",
            (dims["vocab_size"], d), "normal"),
           ("embeddings.position_embeddings.weight",
            (dims["max_position"], d), "normal")]
    for i in range(dims["num_layers"]):
        p = f"blocks.{i}."
        out += [(p + "ln1.weight", (d,), "gain"),
                (p + "ln1.bias", (d,), "normal"),
                (p + "attn.qkv_proj.weight", (d, 3 * d), "normal"),
                (p + "attn.qkv_proj.bias", (3 * d,), "normal"),
                (p + "attn.out_proj.weight", (d, d), "normal"),
                (p + "attn.out_proj.bias", (d,), "normal"),
                (p + "ln2.weight", (d,), "gain"),
                (p + "ln2.bias", (d,), "normal"),
                (p + "mlp.fc1.weight", (d, f), "normal"),
                (p + "mlp.fc1.bias", (f,), "normal"),
                (p + "mlp.fc2.weight", (f, d), "normal"),
                (p + "mlp.fc2.bias", (d,), "normal")]
    out += [("head.ln_f.weight", (d,), "gain"),
            ("head.ln_f.bias", (d,), "normal"),
            ("head.lm_head.weight", (d, dims["vocab_size"]), "normal")]
    return out


def seed_key(seed):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF)


@functools.lru_cache(maxsize=None)
def _maker(specs, dtype):
    import jax
    import jax.numpy as jnp

    def make(key):
        out = {}
        for idx, name, shape, kind in specs:
            v = STD * jax.random.normal(
                jax.random.fold_in(key, idx), shape, jnp.float32)
            if kind == "gain":
                v = 1.0 + v
            out[name] = v.astype(dtype)
        return out
    return jax.jit(make)


def make_weights(seed, dims, dtype, names=None):
    """{name: array of ``dtype``} for ``names`` (all leaves when None),
    in one jitted call.  A leaf's values depend on the seed and on its
    place in ``leaf_specs`` alone, so a subset made later is the same."""
    import jax.numpy as jnp
    specs = tuple((i, n, s, k) for i, (n, s, k)
                  in enumerate(leaf_specs(dims))
                  if names is None or n in names)
    return _maker(specs, jnp.dtype(dtype).name)(seed_key(seed))
