"""Seeded weights, made by the benchmark and never read back from the
program.

The program's model gets these values in one jitted call, in the type it
serves or trains in; the reference makes the same values again from the
seed, leaf by leaf, and upcasts them.  Which leaves there are is the
configuration's business: its program file (``configs/<program>.py``)
lists them as ``[(name, shape, kind)]``, ``kind`` being ``normal``
(std 0.02) or ``gain`` (1 + normal).  A leaf's values depend on the seed
and on its place in that list alone.
"""
from __future__ import annotations

import functools

STD = 0.02


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


def make_weights(seed, leaf_specs, dtype, names=None):
    """{name: array of ``dtype``} for ``names`` (all leaves when None),
    in one jitted call.  A leaf's values depend on the seed and on its
    place in ``leaf_specs`` alone, so a subset made later is the same."""
    import jax.numpy as jnp
    specs = tuple((i, n, tuple(s), k) for i, (n, s, k)
                  in enumerate(leaf_specs)
                  if names is None or n in names)
    return _maker(specs, jnp.dtype(dtype).name)(seed_key(seed))


def fill_model(model, seed, leaf_specs, dtype):
    """Give every parameter of the program's ``model`` its seeded value;
    the program's parameter names and ``leaf_specs`` have to be the same
    set."""
    w = make_weights(seed, leaf_specs, dtype)
    params = dict(model.named_parameters())
    if set(params) != set(w):
        raise RuntimeError(
            "the program's parameters and the benchmark's weights differ: "
            f"{sorted(set(params) ^ set(w))[:6]}")
    for name, p in params.items():
        p.set_value(w[name])
    return model
