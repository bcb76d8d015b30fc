"""Random weights from the seed, made by the benchmark itself.

The program under test is handed these weights; the plain reference
reads the same ones.  Neither takes anything the other made: the tree's
layout (leaf names and shapes) is the program's interface, and every
value comes from here.  One jitted call makes the whole tree on the
device, each leaf in the dtype the program serves it in.

Leaf rules, by the leaf's own name: ``w`` (a projection, ``(..., d_in,
d_out)``) and ``table`` (the embedding) are normal with standard
deviation ``d_in ** -0.5``; ``b`` (a bias) is normal with standard
deviation ``BIAS_STD``, so that a bias left out shows; ``scale`` (a
norm) is ``1 + SCALE_STD`` times a normal, so that a norm scale left
out shows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["key_of", "make_weights", "BIAS_STD", "SCALE_STD"]

BIAS_STD = 0.5
SCALE_STD = 0.2


def key_of(seed: int):
    """A PRNG key from a seed of any size up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0xFFFFFFFF)


def _leaf(key, path, spec):
    name = getattr(path[-1], "key", str(path[-1]))
    shape, dtype = spec.shape, spec.dtype
    z = jax.random.normal(key, shape, jnp.float32)
    if name == "w":
        v = z * shape[-2] ** -0.5
    elif name == "table":
        v = z * shape[-1] ** -0.5
    elif name == "b":
        v = z * BIAS_STD
    elif name == "scale":
        v = 1.0 + SCALE_STD * z
    else:
        raise ValueError(f"no weight rule for leaf {jax.tree_util.keystr(path)}")
    return v.astype(dtype)


def make_weights(shapes, seed: int, device=None):
    """A tree shaped like ``shapes`` (a pytree of ``ShapeDtypeStruct``)
    filled from ``seed``, made on ``device`` in one jitted call."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree_util.tree_unflatten(
            treedef, [_leaf(k, p, s) for k, (p, s) in zip(keys, leaves)])

    kw = {}
    if device is not None:
        kw["out_shardings"] = jax.sharding.SingleDeviceSharding(device)
    return jax.jit(make, **kw)(key_of(seed))
