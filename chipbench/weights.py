"""Random weights for a configuration, made on the device from the seed.

Each architecture (``chipbench/archs/``) builds its whole tree in one
jitted call, in the dtype it is served in, laid out as the serving
program takes it, from these helpers.  The benchmark makes the weights,
not the program, so the plain reference may read them too.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def key_from_seed(seed: int):
    """A PRNG key from any whole number (seeds may exceed 32 bits)."""
    s = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0x7FFFFFFF),
                              (s >> 31) & 0x7FFFFFFF)


def draws(key, n: int, dtype):
    """``normal(shape, std)`` and ``scale(shape)`` (1 + 0.1 normal): each
    call draws one leaf in ``dtype`` from the next of ``n`` subkeys of
    ``key``, so a tree's leaves follow from the order it draws them in."""
    keys = iter(jax.random.split(key, n))

    def normal(shape, std):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * std).astype(dtype)

    def scale(shape):
        return (1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                              jnp.float32)).astype(dtype)
    return normal, scale
