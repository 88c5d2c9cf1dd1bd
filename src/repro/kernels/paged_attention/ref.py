"""Pure-jnp oracle for paged attention: gather pages, dense softmax."""
from __future__ import annotations

import math
from typing import Optional

import jax.numpy as jnp

NEG_INF = -1e30


def paged_attention_ref(q, k_pages, v_pages, block_tables, seq_lens, *,
                        sm_scale: Optional[float] = None):
    """Same contract as the kernel; gathers the head-major paged KV into
    dense (B, K, max_len, D) buffers and runs exact masked attention."""
    b, h, d = q.shape
    n_pages, kh, page_size, _ = k_pages.shape
    group = h // kh
    max_pages = block_tables.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)

    def dense(pages):                                   # (B, K, S, D)
        safe = jnp.maximum(block_tables, 0).reshape(-1)  # (B*maxp,)
        x = jnp.take(pages, safe, axis=0)               # (B*maxp, K, page, D)
        x = x.reshape(b, max_pages, kh, page_size, d).swapaxes(1, 2)
        return x.reshape(b, kh, max_pages * page_size, d)

    k, v = dense(k_pages), dense(v_pages)
    qf = q.reshape(b, kh, group, d).astype(jnp.float32)
    s = jnp.einsum("bkgd,bksd->bkgs", qf, k.astype(jnp.float32)) * sm_scale
    pos = jnp.arange(max_pages * page_size)[None]
    page_ok = jnp.repeat(block_tables >= 0, page_size, axis=1)
    mask = (pos < seq_lens[:, None]) & page_ok
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bkgs,bksd->bkgd", p, v.astype(jnp.float32))
    # rows with no valid position (empty batch slots) attend to nothing
    any_valid = jnp.any(mask, axis=1)                   # (B,)
    o = jnp.where(any_valid[:, None, None, None], o, 0.0)
    return o.reshape(b, h, d).astype(q.dtype)
