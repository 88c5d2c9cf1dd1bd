"""Plain reference of the served models, independent of the program:
the parts every architecture's forward shares (``chipbench/archs/``).

Each forward is written from its published description.  It computes in
float32 with every contraction at ``Precision.HIGHEST``, over the same
bfloat16 weights the program serves, one layer at a time and in blocks
of queries, so it fits beside the program's weights.

``quant=True`` is the control: the same forward with every matmul input
rounded to float8 (e4m3, absmax-scaled per weight matrix and per
activation row), the precision below the stated bfloat16.

The comparison that decides ``correct``: at every position where the
program served a token, how far that token's reference logit lies below
the reference's best (``gaps``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512


def q8(a, axis):
    """Round to float8 e4m3 after absmax scaling over ``axis``."""
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=True)
    s = 448.0 / jnp.maximum(amax, 1e-30)
    return (a * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def mm(a, w, quant):
    """``a @ w`` in float32 at full precision (float8 inputs if quant)."""
    a = a.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant:
        a, w = q8(a, -1), q8(w, None)
    return jnp.matmul(a, w, precision=HI)


def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rope(x, pos, theta):
    """x (S, heads, hd); rotate-half RoPE at absolute positions ``pos``."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None]        # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, quant):
    """Causal GQA attention, query-blocked.  q (S,H,hd), k/v (S,K,hd)."""
    s_len, h, hd = q.shape
    kh = k.shape[1]
    g = h // kh
    qb = min(QUERY_BLOCK, s_len)
    nb = s_len // qb
    if quant:
        q, k, v = q8(q, -1), q8(k, -1), q8(v, -1)
    qs = q.reshape(nb, qb, kh, g, hd)
    kpos = jnp.arange(s_len)

    def block(args):
        i, qblk = args
        sc = jnp.einsum("qkgd,skd->kgqs", qblk, k, precision=HI) * hd ** -0.5
        qpos = i * qb + jnp.arange(qb)
        sc = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None],
                       sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        if quant:
            p = q8(p, -1)
        return jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI)

    out = jax.lax.map(block, (jnp.arange(nb), qs))           # (nb,qb,K,G,hd)
    return out.reshape(s_len, h * hd)


def padded(tokens, rows, pad_to: int):
    """``tokens`` padded to ``pad_to`` (one compiled shape per
    configuration) and ``rows`` padded to a power of two, on the device."""
    n = len(tokens)
    assert n <= pad_to, (n, pad_to)
    toks = np.zeros((pad_to,), np.int32)
    toks[:n] = tokens
    nr = 1
    while nr < len(rows):
        nr *= 2
    r = np.zeros((nr,), np.int32)
    r[:len(rows)] = rows
    return jnp.asarray(toks), jnp.asarray(r)


def gaps(logits_at, weights, dims, prompt, served, *, pad_to: int,
         control: bool = False):
    """Per served token, its reference logit's distance below the best,
    by an architecture's ``logits_at(weights, dims, tokens, rows, *,
    pad_to, quant)``.

    Returns ``(program_gaps, control_gaps)``: the second is None unless
    ``control``, and is the same distance for the token the float8
    forward puts first at each position."""
    tokens = list(prompt) + list(served[:-1])
    rows = np.arange(len(prompt) - 1, len(tokens))
    ref = logits_at(weights, dims, tokens, rows, pad_to=pad_to)
    best = ref.max(axis=-1)
    prog = best - ref[np.arange(len(rows)), np.asarray(served)]
    ctrl = None
    if control:
        low = logits_at(weights, dims, tokens, rows, pad_to=pad_to,
                        quant=True)
        ctrl = best - ref[np.arange(len(rows)), low.argmax(axis=-1)]
    return prog, ctrl
