"""Plain reference of the served models, independent of the program.

A dense decoder forward written from the published description: RMSNorm,
rotary embeddings (rotate-half, as the Llama and Phi-3 code applies
them), grouped-query causal attention, SwiGLU MLP, tied or untied head.
It computes in float32 with every contraction at ``Precision.HIGHEST``,
over the same bfloat16 weights the program serves, one layer at a time
and in blocks of queries, so it fits beside the program's weights.

``quant=True`` is the control: the same forward with every matmul input
rounded to float8 (e4m3, absmax-scaled per weight matrix and per
activation row), the precision below the stated bfloat16.

The comparison that decides ``correct``: at every position where the
program served a token, how far that token's reference logit lies below
the reference's best (``gaps``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.model import Dims

HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512


def _q8(a, axis):
    """Round to float8 e4m3 after absmax scaling over ``axis``."""
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=True)
    s = 448.0 / jnp.maximum(amax, 1e-30)
    return (a * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _mm(a, w, quant):
    """``a @ w`` in float32 at full precision (float8 inputs if quant)."""
    a = a.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant:
        a, w = _q8(a, -1), _q8(w, None)
    return jnp.matmul(a, w, precision=HI)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (S, heads, hd); rotate-half RoPE at absolute positions ``pos``."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None]        # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, quant):
    """Causal GQA attention, query-blocked.  q (S,H,hd), k/v (S,K,hd)."""
    s_len, h, hd = q.shape
    kh = k.shape[1]
    g = h // kh
    qb = min(QUERY_BLOCK, s_len)
    nb = s_len // qb
    if quant:
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, -1)
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
            p = _q8(p, -1)
        return jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI)

    out = jax.lax.map(block, (jnp.arange(nb), qs))           # (nb,qb,K,G,hd)
    return out.reshape(s_len, h * hd)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _layer(x, layers, li, *, dims: Dims, quant: bool):
    lw = jax.tree.map(lambda a: a[li], layers)
    s_len = x.shape[0]
    pos = jnp.arange(s_len)
    hn = _rmsnorm(x, lw["norm1"]["scale"], dims.eps)
    a = lw["attn"]
    q = _mm(hn, a["wq"], quant).reshape(s_len, dims.h, dims.hd)
    k = _mm(hn, a["wk"], quant).reshape(s_len, dims.kv, dims.hd)
    v = _mm(hn, a["wv"], quant).reshape(s_len, dims.kv, dims.hd)
    q, k = _rope(q, pos, dims.theta), _rope(k, pos, dims.theta)
    x = x + _mm(_attention(q, k, v, quant), a["wo"], quant)
    hn = _rmsnorm(x, lw["norm2"]["scale"], dims.eps)
    f = lw["ffn"]
    gate = _mm(hn, f["w_gate"], quant)
    up = _mm(hn, f["w_up"], quant)
    return x + _mm(jax.nn.silu(gate) * up, f["w_down"], quant)


@jax.jit
def _embed(weights, tokens):
    return weights["embed"]["table"][tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _logits(weights, x, rows, *, dims: Dims, quant: bool):
    """Logits (len(rows), vocab) at hidden rows ``rows``."""
    hn = _rmsnorm(x[rows], weights["final_norm"]["scale"], dims.eps)
    head = (weights["embed"]["table"].T if dims.tied
            else weights["lm_head"])
    return _mm(hn, head, quant)[:, :dims.vocab]


def logits_at(weights, dims: Dims, tokens, rows, *, pad_to: int,
              quant: bool = False) -> np.ndarray:
    """Reference logits after ``tokens`` at positions ``rows``.

    The sequence is padded to ``pad_to`` (one compiled shape per
    configuration); ``rows`` is padded to a power of two."""
    n = len(tokens)
    assert n <= pad_to, (n, pad_to)
    toks = np.zeros((pad_to,), np.int32)
    toks[:n] = tokens
    nr = 1
    while nr < len(rows):
        nr *= 2
    r = np.zeros((nr,), np.int32)
    r[:len(rows)] = rows
    x = _embed(weights, jnp.asarray(toks))
    for li in range(dims.layers):
        x = _layer(x, weights["layers"], jnp.int32(li), dims=dims,
                   quant=quant)
    out = _logits(weights, x, jnp.asarray(r), dims=dims, quant=quant)
    return np.asarray(out)[:len(rows)]


def gaps(weights, dims: Dims, prompt, served, *, pad_to: int,
         control: bool = False):
    """Per served token, its reference logit's distance below the best.

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
