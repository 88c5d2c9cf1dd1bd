"""Attention: GQA projections + chunked (memory-bounded) attention.

Three execution paths share one set of weights:
  * ``attend_chunked``   — training / prefill; query-chunked exact softmax so
    the score matrix never materialises beyond (B, H, cq, S) (flash-attention
    memory behaviour in pure jnp — the Pallas kernel in
    ``repro.kernels.flash_attention`` is the TPU hot-spot version).
  * ``attend_decode``    — one new token against a dense KV cache (the
    Pallas ``paged_attention`` kernel is the paged/TPU version).
  * ``attend_decode_swa``— one new token against a ring-buffer window cache.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import layers
from repro.models.sharding import MeshRules

NEG_INF = -1e30


import contextlib


def _null_scope():
    return contextlib.nullcontext()


# ------------------------------------------------------------- weights ----
def attn_init(rng, cfg: ModelConfig, *, dtype=jnp.float32):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, k = cfg.n_heads, cfg.n_kv_heads
    r = jax.random.split(rng, 5)
    p = {
        "wq": layers.dense_init(r[0], d, h * hd, dtype=dtype),
        "wk": layers.dense_init(r[1], d, k * hd, dtype=dtype),
        "wv": layers.dense_init(r[2], d, k * hd, dtype=dtype),
        "wo": layers.dense_init(r[3], h * hd, d, dtype=dtype,
                                scale=1.0 / (h * hd) ** 0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = layers.bias_init(h * hd, dtype=dtype)
        p["bk"] = layers.bias_init(k * hd, dtype=dtype)
        p["bv"] = layers.bias_init(k * hd, dtype=dtype)
    return p


def attn_specs(cfg: ModelConfig, rules: MeshRules) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, k = cfg.n_heads, cfg.n_kv_heads
    # Shard the flattened head dim on `model` only when whole heads divide,
    # so per-head softmax stays device-local.
    q_tp = rules.tp_axis if (rules.tp_size and h % rules.tp_size == 0) else None
    kv_tp = rules.tp_axis if (rules.tp_size and k % rules.tp_size == 0) else None
    s = {
        "wq": P(rules.fsdp(d), q_tp),
        "wk": P(rules.fsdp(d), kv_tp),
        "wv": P(rules.fsdp(d), kv_tp),
        "wo": P(q_tp, rules.fsdp(d)),
    }
    if cfg.qkv_bias:
        s["bq"] = P(q_tp)
        s["bk"] = P(kv_tp)
        s["bv"] = P(kv_tp)
    return s


def qkv_proj(params, cfg: ModelConfig, x):
    """x: (B, S, D) -> q (B,S,H,hd), k,v (B,S,K,hd)."""
    hd = cfg.resolved_head_dim
    b, s, _ = x.shape
    q = x @ params["wq"].astype(x.dtype)
    k = x @ params["wk"].astype(x.dtype)
    v = x @ params["wv"].astype(x.dtype)
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    return (q.reshape(b, s, cfg.n_heads, hd),
            k.reshape(b, s, cfg.n_kv_heads, hd),
            v.reshape(b, s, cfg.n_kv_heads, hd))


def out_proj(params, cfg: ModelConfig, att):
    b, s = att.shape[:2]
    return att.reshape(b, s, -1) @ params["wo"].astype(att.dtype)


# ----------------------------------------------------- chunked attention ---
def _chunk_scores(q, k, scale):
    """q (B,cq,K,G,hd), k (B,Sk,K,hd) -> scores (B,K,G,cq,Sk) fp32."""
    return jnp.einsum("bqkgh,bskh->bkgqs", q, k,
                      preferred_element_type=jnp.float32) * scale


def attend_chunked(q, k, v, *, causal: bool = True, window: int = 0,
                   q_offset: int = 0, chunk: int = 512,
                   fused: bool = False):
    """Exact attention, query-chunked.  q (B,Sq,H,hd); k,v (B,Sk,K,hd).

    ``q_offset``: absolute position of q[0] relative to k[0] (prefill with a
    pre-existing cache).  ``window`` > 0 applies a sliding window (SWA).
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    kh = k.shape[2]
    g = h // kh
    scale = hd ** -0.5
    chunk = min(chunk, sq)
    # pad sq to a multiple of chunk
    pad = (-sq) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nq = q.shape[1] // chunk
    qs = q.reshape(b, nq, chunk, h, hd).transpose(1, 0, 2, 3, 4)

    kpos = jnp.arange(sk)

    def one_chunk(carry, inp):
        ci, qc = inp
        # Under the fused contract this region executes as the Pallas
        # flash-attention kernel on TPU (repro.kernels.flash_attention);
        # the scope marker tells the HLO cost walker its interior never
        # touches HBM (boundary bytes are added back analytically).
        scope = (jax.named_scope("vmem_fused_flash") if fused
                 else _null_scope())
        with scope:
            # FLAT-HEAD einsums: factoring H into (K, G) breaks the TP
            # head sharding (the mesh axis cannot split either factor
            # evenly for e.g. 8 kv heads on 16 shards) and makes XLA
            # partial-sum full activations per chunk.  Expanding KV to H
            # heads keeps every einsum head-local; the expansion itself
            # is kernel-interior (the Pallas kernel indexes KV by
            # h // group without materializing it).
            if g > 1:
                ke = jnp.repeat(k, g, axis=2)          # (B,Sk,H,hd)
                ve = jnp.repeat(v, g, axis=2)
            else:
                ke, ve = k, v
            scores = jnp.einsum("bqhd,bshd->bhqs", qc, ke,
                                preferred_element_type=jnp.float32) * scale
            qpos = q_offset + ci * chunk + jnp.arange(chunk)
            mask = jnp.ones((chunk, sk), dtype=bool)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window:
                mask &= kpos[None, :] > (qpos[:, None] - window)
            scores = jnp.where(mask[None, None], scores, NEG_INF)
            att = jax.nn.softmax(scores, axis=-1).astype(ve.dtype)
            out = jnp.einsum("bhqs,bshd->bqhd", att, ve)
        return carry, out

    _, outs = jax.lax.scan(one_chunk, None,
                           (jnp.arange(nq), qs))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(b, nq * chunk, h, hd)
    return out[:, :sq]


# -------------------------------------------------------------- decode ----
def attend_decode(q, k_cache, v_cache, cache_len, *, fused: bool = False):
    """q (B,1,H,hd); caches (B,Smax,K,hd); cache_len (B,) valid entries
    (including the token written this step)."""
    b, _, h, hd = q.shape
    smax, kh = k_cache.shape[1], k_cache.shape[2]
    g = h // kh
    scale = hd ** -0.5
    qc = q.reshape(b, 1, kh, g, hd)
    # fused contract: runs as the paged/flash decode Pallas kernel on TPU
    scope = (jax.named_scope("vmem_fused_decode") if fused
             else _null_scope())
    with scope:
        scores = jnp.einsum("bqkgh,bskh->bkgqs", qc, k_cache,
                            preferred_element_type=jnp.float32) * scale
        pos = jnp.arange(smax)
        mask = pos[None, :] < cache_len[:, None]      # (B,Smax)
        scores = jnp.where(mask[:, None, None, None, :], scores, NEG_INF)
        att = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
        out = jnp.einsum("bkgqs,bskh->bqkgh", att, v_cache)
    return out.reshape(b, 1, h, hd)


def attend_decode_cp(q, k_cache, v_cache, cache_len, mesh, *,
                     seq_axis: str = "model", batch_axes=("data",),
                     fused: bool = False):
    """Context-parallel decode attention: the KV cache stays SEQUENCE-
    sharded on the `model` axis and the softmax is computed distributed
    (pmax/psum of per-shard stats) instead of letting the partitioner
    all-gather the cache — 10.8 GB/step -> ~100 MB/step of ICI traffic for
    qwen2-72b decode_32k (EXPERIMENTS.md §Perf, hillclimb #3).

    q (B,1,H,hd) replicated over `model`; caches (B,KL,K,hd) KL-sharded on
    `model`; cache_len (B,).  Inside shard_map the local block is the
    paged/flash decode Pallas kernel region (fused contract scope).
    """
    from jax.sharding import PartitionSpec as P

    b, _, h, hd = q.shape
    kh = k_cache.shape[2]
    g = h // kh
    scale = hd ** -0.5
    n_seq = mesh.shape[seq_axis]
    bax = batch_axes[0] if b % mesh.shape[batch_axes[0]] == 0 else None

    def local(qb, kc, vc, clen):
        s_local = kc.shape[1]
        idx = jax.lax.axis_index(seq_axis)
        scope = (jax.named_scope("vmem_fused_decode") if fused
                 else _null_scope())
        with scope:
            qc = qb.reshape(qb.shape[0], 1, kh, g, hd)
            scores = jnp.einsum("bqkgh,bskh->bkgqs", qc, kc,
                                preferred_element_type=jnp.float32) * scale
            pos = idx * s_local + jnp.arange(s_local)
            mask = pos[None, :] < clen[:, None]
            scores = jnp.where(mask[:, None, None, None, :], scores,
                               NEG_INF)
            m_loc = jnp.max(scores, axis=-1, keepdims=True)
            m = jax.lax.pmax(m_loc, seq_axis)
            p = jnp.exp(scores - m)
            l = jax.lax.psum(jnp.sum(p, axis=-1, keepdims=True), seq_axis)
            part = jnp.einsum("bkgqs,bskh->bqkgh", p, vc,
                              preferred_element_type=jnp.float32)
            out = jax.lax.psum(part, seq_axis)
        out = out / jnp.maximum(l.transpose(0, 3, 1, 2, 4), 1e-30)
        return out.reshape(qb.shape[0], 1, h, hd).astype(qb.dtype)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(bax, None, None, None), P(bax, seq_axis, None, None),
                  P(bax, seq_axis, None, None), P(bax)),
        out_specs=P(bax, None, None, None),
        check_vma=False,
    )(q, k_cache, v_cache, cache_len)


def cache_update(k_cache, v_cache, k_new, v_new, cache_len):
    """Write one token at position cache_len (per batch row)."""
    b = k_cache.shape[0]
    idx = cache_len  # (B,)
    k_cache = jax.vmap(
        lambda c, kn, i: jax.lax.dynamic_update_slice(c, kn, (i, 0, 0))
    )(k_cache, k_new, idx)
    v_cache = jax.vmap(
        lambda c, vn, i: jax.lax.dynamic_update_slice(c, vn, (i, 0, 0))
    )(v_cache, v_new, idx)
    return k_cache, v_cache


def cache_update_uniform(k_cache, v_cache, k_new, v_new, pos):
    """All rows write at the SAME position (static-batch decode): one
    in-place dynamic_update_slice instead of a per-row scatter.  Avoids
    XLA's scatter expansion (which converts the full stacked cache) — the
    decode hillclimb's first win (EXPERIMENTS.md §Perf)."""
    upd_k = k_new.astype(k_cache.dtype)
    upd_v = v_new.astype(v_cache.dtype)
    zero = jnp.zeros((), jnp.int32)
    k_cache = jax.lax.dynamic_update_slice(k_cache, upd_k,
                                           (zero, pos, zero, zero))
    v_cache = jax.lax.dynamic_update_slice(v_cache, upd_v,
                                           (zero, pos, zero, zero))
    return k_cache, v_cache


def cache_update_ring(k_cache, v_cache, k_new, v_new, pos):
    """SWA ring buffer of size W: write at pos % W."""
    w = k_cache.shape[1]
    slot = pos % w
    k_cache = jax.vmap(
        lambda c, kn, i: jax.lax.dynamic_update_slice(c, kn, (i, 0, 0))
    )(k_cache, k_new, slot)
    v_cache = jax.vmap(
        lambda c, vn, i: jax.lax.dynamic_update_slice(c, vn, (i, 0, 0))
    )(v_cache, v_new, slot)
    return k_cache, v_cache


def attend_decode_swa(q, k_cache, v_cache, pos, window: int):
    """Decode against a ring-buffer cache of size W=window.

    ``pos`` (B,): absolute position of the current token (already written).
    Valid entries: absolute positions in (pos-W, pos]; slot i holds the most
    recent token with abs_pos % W == i.
    """
    b, _, h, hd = q.shape
    w, kh = k_cache.shape[1], k_cache.shape[2]
    g = h // kh
    scale = hd ** -0.5
    qc = q.reshape(b, 1, kh, g, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qc, k_cache,
                        preferred_element_type=jnp.float32) * scale
    slots = jnp.arange(w)
    # slot i holds abs position: pos - ((pos - i) mod W)
    abs_pos = pos[:, None] - ((pos[:, None] - slots[None, :]) % w)
    valid = (abs_pos >= 0) & (abs_pos > pos[:, None] - w) & (abs_pos <= pos[:, None])
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    att = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", att, v_cache)
    return out.reshape(b, 1, h, hd)
