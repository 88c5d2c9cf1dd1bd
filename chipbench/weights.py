"""Random weights for a configuration, made on the device from the seed.

One jitted call builds the whole tree in the dtype it is served in, laid
out as the serving program takes it: ``embed.table`` (padded vocab, D),
``lm_head`` (D, padded vocab) unless tied, ``final_norm.scale``, and the
layers stacked on a leading axis (``norm1``, ``attn.wq/wk/wv/wo``,
``norm2``, ``ffn.w_gate/w_up/w_down``).  The benchmark makes these, not
the program, so the plain reference may read them too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.model import Dims


def key_from_seed(seed: int):
    """A PRNG key from any whole number (seeds may exceed 32 bits)."""
    s = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0x7FFFFFFF),
                              (s >> 31) & 0x7FFFFFFF)


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _make(key, *, dims: Dims, dtype):
    d, hd, h, k, f = dims.d, dims.hd, dims.h, dims.kv, dims.ff
    nl, vp = dims.layers, dims.padded_vocab
    keys = iter(jax.random.split(key, 12))

    def normal(shape, std):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * std).astype(dtype)

    def scale(shape):
        return (1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                              jnp.float32)).astype(dtype)

    tree = {
        "embed": {"table": normal((vp, d), 0.02)},
        "final_norm": {"scale": scale((d,))},
        "layers": {
            "norm1": {"scale": scale((nl, d))},
            "norm2": {"scale": scale((nl, d))},
            "attn": {"wq": normal((nl, d, h * hd), d ** -0.5),
                     "wk": normal((nl, d, k * hd), d ** -0.5),
                     "wv": normal((nl, d, k * hd), d ** -0.5),
                     "wo": normal((nl, h * hd, d), (h * hd) ** -0.5)},
            "ffn": {"w_gate": normal((nl, d, f), d ** -0.5),
                    "w_up": normal((nl, d, f), d ** -0.5),
                    "w_down": normal((nl, f, d), f ** -0.5)},
        },
    }
    if not dims.tied:
        tree["lm_head"] = normal((d, vp), d ** -0.5)
    return tree


def make_weights(dims: Dims, seed: int, dtype=jnp.bfloat16):
    return _make(key_from_seed(seed), dims=dims, dtype=dtype)
