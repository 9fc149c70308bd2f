"""Seeded random weights, made on the device in one jitted call.

The benchmark, not the program, makes the weights: they stand for a
checkpoint that is loaded into the server, and the plain reference in
``reference.py`` reads the same arrays. Which leaves there are, and their
shapes, is the configuration's layout (``layouts/<layout>.py``,
``shapes``): the program's parameter tree, ``groups`` stacked on a
leading depth axis, ``tail`` layers each on its own. ``serve.build``
checks it against the program's own ``init_lm`` shapes before serving.

Scales follow the usual fan-in rule; norm scales are drawn around 1 so
that a norm whose scale is dropped shows in the comparison.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def key_of(seed: int, salt: int) -> jax.Array:
    """A PRNG key for any whole ``seed`` (more bits than 32 kept)."""
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    k = jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(k, salt)


def _draw(key, shape, kind, dtype):
    if kind == "norm":
        x = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    else:
        x = jax.random.normal(key, shape, jnp.float32) * float(kind) ** -0.5
    return x.astype(dtype)


def _nest(flat):
    tree = {}
    for path, leaf in flat.items():
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = leaf
    return tree


@partial(jax.jit, static_argnums=(1,))
def _make(key, spec):
    table, dtype = spec
    flat = {path: _draw(jax.random.fold_in(key, i), shape, kind, dtype)
            for i, (path, shape, kind) in enumerate(table)}
    return _nest(flat)


def model_weights(layout, cfg, seed: int):
    """The model's weights in the served dtype, from ``seed``; leaves as
    ``layout.shapes(cfg)`` lists them, each drawn with its index there."""
    table = tuple((p, s, k) for p, (s, k) in layout.shapes(cfg).items())
    tree = _make(key_of(seed, 1), (table, jnp.dtype(cfg.param_dtype)))
    # the program's tree holds a tail, empty where there are no tail layers
    tree.setdefault("tail", {})
    return tree


def g_weights(cfg, seed: int, rank: int, out_std: float,
              n_fourier: int = 8):
    """The hypersolver correction g (``models/cdepth.py`` layout, f32),
    every weight drawn from the seed. The readout's scale ``out_std`` is
    the cell's: the probe's error estimate, rms(g) at s = 0, comes to
    about out_std * sqrt(rank) (the tanh saturates on the field's scale),
    and the cell's ``tol`` turns that into its bucket."""
    d = cfg.d_model
    # kinds are fan-ins: out_std ** -2 draws the readout with std out_std
    table = ((("w_h",), (d, rank), d), (("w_dh",), (d, rank), d),
             (("w_s",), (2 * n_fourier + 1, rank), 11.0),
             (("w_out",), (rank, d), float(out_std) ** -2))
    return _make(key_of(seed, 2), (table, jnp.dtype(jnp.float32)))
