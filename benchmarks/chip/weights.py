"""Random weights for a configuration, drawn from the run's seed.

One jitted call makes the whole tree on the device, in the dtype it is
served in, in the layout the program's family reads.  The family module
(families/<family>.py, found by the configuration's ``model.family``)
declares the tree: ``leaves(cfg)`` gives each leaf's path, shape and rule,
and a dtype where the leaf is not served in ``cfg.param_dtype``.  A rule is
one of RULES by name:

    ones     norm scales
    zeros    biases
    embed    N(0, 0.02**2)
    fan_in   N(0, 1 / shape[-2]): a matrix [..., fan_in, fan_out]

or, for a leaf those do not fit (the decay of a state-space layer), a
function ``(key, shape, dtype) -> array`` of the family's own.  Leaves are
drawn in the order of their sorted paths, leaf i from ``fold_in(key, i)``.

Both the program under test and the reference get their weights from here,
so the reference takes nothing the program made: it draws the same tree
again from the seed after the window.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import spec


def _normal(key, shape, scale: float, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


RULES = {
    "ones": lambda key, shape, dtype: jnp.ones(shape, dtype),
    "zeros": lambda key, shape, dtype: jnp.zeros(shape, dtype),
    "embed": lambda key, shape, dtype: _normal(key, shape, 0.02, dtype),
    "fan_in": lambda key, shape, dtype: _normal(key, shape,
                                                shape[-2] ** -0.5, dtype),
}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any seed up to 64 bits."""
    seed = int(seed)
    return jax.random.wrap_key_data(
        np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32))


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        *heads, last = path.split("/")
        node = tree
        for h in heads:
            node = node.setdefault(h, {})
        node[last] = leaf
    return tree


@functools.partial(jax.jit, static_argnums=(1,))
def _generate(key, cfg):
    flat = {}
    leaves = spec.family(cfg.family).leaves(cfg)
    for i, (path, (shape, rule, *dtype)) in enumerate(sorted(leaves.items())):
        draw = RULES[rule] if isinstance(rule, str) else rule
        flat[path] = draw(jax.random.fold_in(key, i), shape,
                          jnp.dtype(dtype[0] if dtype else cfg.param_dtype))
    return _nest(flat)


def generate(cfg, seed: int) -> dict:
    """The weight tree for ``cfg`` drawn from ``seed``, on the device."""
    return _generate(seed_key(seed), cfg)
