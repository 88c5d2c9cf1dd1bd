"""Decode path through the MMU's paged KV pools.

The serving twin of ``repro.models.transformer.decode_step``: instead of a
dense per-sequence cache, KV lives in the MMU service's page pools and
attention walks the block tables (via the Pallas paged-attention kernel or
its oracle).

Hot-path contract (device-resident decode):

  * **Flat, head-major pool layout.**  The pools are a single
    ``(n_layers * n_pages, kv_heads, page_size, head_dim)`` buffer per
    side; layer ``l``'s physical page ``p`` lives at flat slot
    ``l * n_pages + p``.  This lets the pools ride the decode scan as an
    *aliased loop carry* — per-layer KV appends are in-place
    dynamic-updates into one buffer — instead of as scan inputs/outputs,
    which would force a full pool copy every step.  Per-layer access is
    pure page-id arithmetic (bias the block table by ``l * n_pages``), so
    the paged-attention kernel is unchanged.  Head-major puts one
    (page, kv head) block in the last two dims as a ``(page_size,
    head_dim)`` tile, which is what the compiled TPU kernel DMAs.
  * **Donation.**  ``pools`` (and the decode-state buffers lens /
    last_tokens / rng) are donated into the jitted steps — KV is updated
    in place, never copied.  Callers must drop their reference and adopt
    the returned arrays (the engine reassigns ``self.pools`` etc. every
    step).
  * **Fused sampling.**  Greedy argmax and Gumbel-max temperature
    sampling happen inside the jit, so the (B, vocab) logits tensor never
    crosses to the host — the step returns only a (B,) int32 token
    vector.
  * ``prefill_paged`` admits a whole batch of new requests in one padded
    forward pass and scatters their KV into the pools in the same jit.

Applicability: attention-family architectures.  SSM archs have O(1) decode
state and bypass paging (DESIGN.md §5 — their MMU use is the constant-size
state page).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels.paged_attention.ops import paged_decode
from repro.models import attention, layers, mlp, moe
from repro.models.transformer import _is_moe_layer, forward, lm_logits
from repro.serve.sampler import fold_row_keys, sample_per_row

# Trace-time counters, keyed by function name.  Incremented as a Python
# side effect while tracing, so a test (or an operator) can assert that a
# hot-path function compiled exactly once across a run — the retrace guard
# for the device-resident decode contract.
TRACE_COUNTS: Dict[str, int] = {}


def _count_trace(name: str) -> None:
    TRACE_COUNTS[name] = TRACE_COUNTS.get(name, 0) + 1


def make_pools(cfg: ModelConfig, n_pages: int, page_size: int, *,
               dtype=jnp.float32, kv_sharding=None) -> Dict[str, jnp.ndarray]:
    """Flat head-major KV pools: layer ``l``'s page ``p`` is flat slot
    ``l * n_pages + p`` of a (n_layers * n_pages, K, page, hd) buffer.

    ``kv_sharding``: optional ``NamedSharding`` for tensor-parallel
    serving — the canonical TP layout shards axis 1 (``kv_heads``) on the
    mesh's ``model`` axis (``P(None, "model", None, None)``), so each
    device holds every page but only its head slice and paged attention
    needs no collective (softmax is head-local).  The page-id geometry is
    unchanged: block tables, the pager, and migration stay shard-agnostic.
    """
    hd = cfg.resolved_head_dim
    shape = (cfg.n_layers * n_pages, cfg.n_kv_heads, page_size, hd)
    pools = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if kv_sharding is not None:
        pools = {s: jax.device_put(p, kv_sharding)
                 for s, p in pools.items()}
    return pools


def _append_kv(pool, page, off, new, active):
    """Write one decode token's KV per row into a head-major pool.

    page/off (B,) flat page slot and in-page offset; new (B, K, hd);
    inactive rows leave the pool as it was.  One dynamic-update-slice
    per (row, kv head) into a ``(slots * K * page, hd)`` view of the
    pool, rather than a scatter or a 4-d slice: XLA's TPU backend lays a
    scattered (or strided-slab) pool out with the written dims minor, so
    it would relayout the whole pool at every layer around the
    paged-attention kernel, which reads it row-major."""
    n_flat, kh, page_size, hd = pool.shape
    rows = pool.reshape(n_flat * kh * page_size, hd)
    for b in range(new.shape[0]):
        for k in range(kh):
            at = ((page[b] * kh + k) * page_size + off[b], 0)  # XLA clamps
            old = jax.lax.dynamic_slice(rows, at, (1, hd))
            upd = new[b, k][None].astype(pool.dtype)
            rows = jax.lax.dynamic_update_slice(
                rows, jnp.where(active[b], upd, old), at)
    return rows.reshape(pool.shape)


def write_prefill(pools, layer_kv, tables, lens, page_size: int):
    """Scatter a prefilled sequence batch into the flat pools.

    layer_kv: (ks, vs) each (L, B, S, K, hd); tables (B, maxp) int32
    per-layer page ids; lens (B,) prompt lengths.  One scatter per side:
    tokens at/after a row's len (padding) and positions whose table entry
    is unmapped are routed to an out-of-bounds flat slot and dropped by
    the scatter (``mode="drop"``) — no gather of the existing pool
    contents is needed.
    """
    ks, vs = layer_kv
    l, b, s, kh, hd = ks.shape
    n_flat = pools["k"].shape[0]
    n_pages = n_flat // l
    pos = jnp.arange(s)
    vpage = pos // page_size                         # (S,)
    off = pos % page_size
    ppage = jnp.take_along_axis(
        tables, jnp.broadcast_to(vpage[None], (b, s)), axis=1)  # (B,S)
    valid = (pos[None, :] < lens[:, None]) & (ppage >= 0)       # (B,S)
    base = (jnp.arange(l) * n_pages)[:, None, None]             # (L,1,1)
    # invalid writes point one past the pool end: dropped by mode="drop"
    flat_page = jnp.where(valid[None], base + ppage[None], n_flat)
    flat_page = flat_page.reshape(-1)                # (L*B*S,)
    flat_off = jnp.broadcast_to(
        jnp.broadcast_to(off[None], (b, s)).reshape(-1)[None],
        (l, b * s)).reshape(-1)

    def write(pool, new):
        upd = new.reshape(l * b * s, kh, hd).astype(pool.dtype)
        return pool.at[flat_page, :, flat_off].set(upd, mode="drop")

    return {"k": write(pools["k"], ks), "v": write(pools["v"], vs)}


def flat_page_indices(ppages, n_layers: int, n_pages: int) -> jnp.ndarray:
    """Flat pool slots of physical pages ``ppages`` across every layer.

    Layer ``l``'s copy of page ``p`` lives at flat slot ``l*n_pages + p``
    (the pool layout contract above), so the result is layer-major:
    ``[l0p0, l0p1, ..., l1p0, ...]`` with shape ``(n_layers * len(ppages),)``.
    Both the migration gather and the evict-with-copy pager use this
    ordering — gather and scatter MUST agree on it for KV bytes to land
    back on the right (layer, page) after a move.
    """
    pp = jnp.asarray(ppages, jnp.int32).reshape(-1)
    base = jnp.arange(n_layers, dtype=jnp.int32)[:, None] * n_pages
    return (base + pp[None, :]).reshape(-1)


def bucket_pages(n: int, *, floor: int = 4) -> int:
    """Round a page-transfer count up to the next power of two (at least
    ``floor``).  The pre-copy freeze window gathers/scatters the dirty
    delta, whose size jitters by a page or two between moves — padding
    the transfer to a bucket makes those shapes collide, so the compiled
    gather/scatter is reused instead of retraced inside the downtime
    window (pad pages repeat the last real page; a duplicate scatter of
    identical rows is a no-op)."""
    b = max(int(floor), 1)
    while b < n:
        b <<= 1
    return b


@jax.jit
def gather_kv_pages(pools, flat_idx):
    """Device-side compact gather of live KV pages.

    ``flat_idx`` (n,) int32 flat pool slots (see :func:`flat_page_indices`);
    returns ``{"k": (n, K, page, hd), "v": ...}`` — the transfer buffer a
    migration snapshot ships, and the payload the MMU pager preserves on
    evict.  Pools are NOT donated (the source keeps serving until the
    move commits).  Retraces per distinct gather size — this is the cold
    control path, not the decode loop.
    """
    _count_trace("gather_kv_pages")
    return {"k": jnp.take(pools["k"], flat_idx, axis=0),
            "v": jnp.take(pools["v"], flat_idx, axis=0)}


@functools.partial(jax.jit, donate_argnames=("pools",))
def scatter_kv_pages(pools, flat_idx, data):
    """Scatter a gathered transfer buffer back into (donated) pools at
    ``flat_idx`` — the restore half of migration and of the pager's
    fault-back-in.  ``data`` must use :func:`flat_page_indices` ordering."""
    _count_trace("scatter_kv_pages")
    return {"k": pools["k"].at[flat_idx].set(
                data["k"].astype(pools["k"].dtype)),
            "v": pools["v"].at[flat_idx].set(
                data["v"].astype(pools["v"].dtype))}


def pack_slot_delta(max_batch: int, slots, rows) -> np.ndarray:
    """The host half of :func:`update_slot_state`: one (7, max_batch)
    int32 array holding a row mask, then rids, lens, tokens, top_k, and
    temperature and top_p as float32 bit patterns.  ``rows`` is a list
    of (len, token, temperature, top_k, top_p, rid), one per slot in
    ``slots``; every other row's mask is 0."""
    packed = np.zeros((7, max_batch), np.int32)
    for i, (ln, tok, temp, topk, topp, rid) in zip(slots, rows):
        packed[:, i] = (1, rid, ln, tok, topk,
                        np.float32(temp).view(np.int32),
                        np.float32(topp).view(np.int32))
    return packed


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4, 5))
def update_slot_state(rids, lens, tokens, temps, topk, topp, packed):
    """Write a slot-state delta (:func:`pack_slot_delta`) into the six
    (B,) decode-state arrays; rows whose mask is 0 keep their values.
    The shapes never vary with the number of rows written, so the
    program compiles once per batch size.  The six arrays are donated
    and come back in the same order."""
    _count_trace("slot_update")
    take = packed[0] != 0

    def bits(x):
        return jax.lax.bitcast_convert_type(x, jnp.float32)
    return (jnp.where(take, packed[1], rids),
            jnp.where(take, packed[2], lens),
            jnp.where(take, packed[3], tokens),
            jnp.where(take, bits(packed[5]), temps),
            jnp.where(take, packed[4], topk),
            jnp.where(take, bits(packed[6]), topp))


@functools.partial(jax.jit, static_argnames=("cfg", "page_size"),
                   donate_argnames=("pools", "rng"))
def prefill_paged(params, pools, tokens, lens, tables, rng, temperatures,
                  top_k=None, top_p=None,
                  *, cfg: ModelConfig, page_size: int):
    """Batched prefill: one padded forward for every admitted request.

    tokens (N, S) int32 right-padded prompts; lens (N,) prompt lengths
    (0 = padding row); tables (N, maxp) block tables for the freshly
    allocated sequences; temperatures (N,); optional per-request top_k
    (N,) int32 / top_p (N,) float32 sampling filters.  Returns
    (first_tokens (N,) int32, new_pools, new_rng).  ``pools`` and ``rng``
    are donated; sampling happens on device (padding rows yield garbage
    tokens the caller ignores).
    """
    _count_trace("prefill_paged")
    n = tokens.shape[0]
    hidden, _, kv_stack, _ = forward(params, cfg, tokens, collect_kv=True)
    pools = write_prefill(pools, kv_stack, tables, lens, page_size)
    last = hidden[jnp.arange(n), jnp.maximum(lens - 1, 0)]      # (N, D)
    logits = lm_logits(params, cfg, last)[..., :cfg.vocab_size]
    rng, sub = jax.random.split(rng)
    first = sample_per_row(sub, logits, temperatures, top_k, top_p)
    return first, pools, rng


def _attend_pages(q, kp, vp, tables, base, mask, *, cfg: ModelConfig):
    """Exact attention of prefill queries over a dense gather of their
    pages (ref-oracle style).

    q (N, T, H, hd); kp/vp the flat head-major pools; tables (N, maxp)
    per-layer page ids; ``base`` the layer's flat-slot offset; mask
    (N, T, S) with S = maxp * page_size.  Returns (N, T, H, hd) float32;
    a query with no visible key attends to nothing."""
    n, t = q.shape[:2]
    kh = cfg.n_kv_heads
    g = cfg.n_heads // kh
    maxp = tables.shape[1]
    safe = (jnp.maximum(tables, 0) + base).reshape(-1)

    def dense(pool):                                    # (N, K, S, hd)
        x = jnp.take(pool, safe, axis=0)                # (N*maxp, K, pg, hd)
        x = x.reshape(n, maxp, kh, *pool.shape[2:]).swapaxes(1, 2)
        return x.reshape(n, kh, -1, pool.shape[-1]).astype(jnp.float32)

    qf = q.reshape(n, t, kh, g, -1).astype(jnp.float32)
    s = jnp.einsum("ntkgd,nksd->nkgts", qf, dense(kp)) * (
        cfg.resolved_head_dim ** -0.5)
    s = jnp.where(mask[:, None, None], s, attention.NEG_INF)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    att = jnp.einsum("nkgts,nksd->ntkgd", p, dense(vp))
    any_ok = jnp.any(mask, axis=-1)                     # (N,T)
    att = jnp.where(any_ok[:, :, None, None, None], att, 0.0)
    return att.reshape(n, t, cfg.n_heads, -1)


def _prefill_shared_impl(params, pools, tokens, q_lens, q_starts,
                         write_from, tables, rng, temperatures,
                         top_k=None, top_p=None, seq_ids=None,
                         *, cfg: ModelConfig, page_size: int,
                         psum_attn=None, psum_mlp=None):
    """Suffix prefill for prefix-shared admissions.

    When the MMU maps a prompt's leading pages onto already-resident
    shared pages (``alloc_seq(..., prompt_tokens=...)``), only the
    *uncovered suffix* needs a forward pass: the shared pages already
    hold the exact KV those positions would produce.  This kernel runs
    the transformer over just the suffix tokens, attending through the
    block tables (so queries see the shared prefix KV), and scatters
    new KV only at positions >= ``write_from`` — shared pages are never
    written, preserving them for their other owners.

    tokens (N, T) int32   — suffix tokens, right-padded; row i holds
                            prompt[q_starts[i] : q_starts[i]+q_lens[i]];
    q_lens (N,) int32     — suffix lengths (0 = padding row);
    q_starts (N,) int32   — absolute position of tokens[i, 0].  For a
                            fully covered prompt this is len-1: the last
                            token's query is recomputed to produce
                            logits, but its KV write is masked off;
    write_from (N,) int32 — absolute position from which KV is written
                            (= tokens covered by shared pages);
    tables (N, maxp)      — block tables for the full prompt (shared
                            prefix pages + freshly allocated suffix).

    Returns (first_tokens (N,) int32, new_pools, new_rng); ``pools`` and
    ``rng`` are donated.  Retraces per (N, T, maxp) bucket — admission
    is the cold path, so this mirrors ``prefill_paged``'s bucketing.

    ``seq_ids`` (N,) int32, optional: when given, sampling keys are
    counter-based — ``fold_in(fold_in(rng, seq_id), prompt_len)`` per
    row instead of one batch-wide split — so a request's first token is
    identical however admission batched or chunked its prefill, and
    ``rng`` passes through unconsumed.

    ``psum_attn`` / ``psum_mlp``: optional reduction hooks for the
    tensor-parallel path (``repro.serve.tp``) — called on the out-proj /
    FFN partial sums when this body runs inside shard_map with
    head-/hidden-sharded weights.  None (the default) is the
    single-device path, byte-for-byte the pre-TP behaviour.
    """
    n, t = tokens.shape
    maxp = tables.shape[1]
    n_flat = pools["k"].shape[0]
    n_pages = n_flat // cfg.n_layers
    pos = q_starts[:, None] + jnp.arange(t)[None, :]        # (N,T) absolute
    qvalid = jnp.arange(t)[None, :] < q_lens[:, None]
    kv_lens = q_starts + q_lens                             # full prompt len
    vpage = jnp.minimum(pos // page_size, maxp - 1)
    off = pos % page_size
    ppage = jnp.take_along_axis(tables, vpage, axis=1)      # (N,T)
    wvalid = qvalid & (pos >= write_from[:, None]) & (ppage >= 0)
    kpos = jnp.arange(maxp * page_size)[None]               # (1,S)
    page_ok = jnp.repeat(tables >= 0, page_size, axis=1)    # (N,S)
    kv_ok = (kpos < kv_lens[:, None]) & page_ok             # (N,S)
    mask = kv_ok[:, None, :] & (kpos[:, None, :] <= pos[:, :, None])

    x = layers.embed_lookup(params["embed"], tokens)        # (N,T,D)

    def body(carry, inp):
        x, kp, vp = carry
        li, lp = inp
        base = li * n_pages
        h = layers.norm_apply(lp["norm1"], x, cfg.norm_eps)
        q, k, v = attention.qkv_proj(lp["attn"], cfg, h)
        if cfg.pos_embed == "rope":
            q = layers.apply_rope(q, pos, cfg.rope_theta)
            k = layers.apply_rope(k, pos, cfg.rope_theta)
        # scatter suffix KV first so suffix queries see their own keys;
        # masked-off writes (shared-prefix positions, padding, unmapped
        # pages) drop at the out-of-bounds slot
        drop_page = jnp.where(wvalid, base + ppage, n_flat)
        kp = kp.at[drop_page, :, off].set(k.astype(kp.dtype), mode="drop")
        vp = vp.at[drop_page, :, off].set(v.astype(vp.dtype), mode="drop")
        # attend over the full paged KV (shared prefix + fresh suffix)
        att = _attend_pages(q, kp, vp, tables, base, mask, cfg=cfg)
        o = attention.out_proj(lp["attn"], cfg, att.astype(x.dtype))
        if psum_attn is not None:
            o = psum_attn(o)
        x = x + o
        h = layers.norm_apply(lp["norm2"], x, cfg.norm_eps)
        if _is_moe_layer(cfg):
            out, _ = moe.moe_apply(lp["ffn"], cfg, h)
        else:
            out = mlp.mlp_apply(lp["ffn"], cfg, h)
        if psum_mlp is not None:
            out = psum_mlp(out)
        return (x + out, kp, vp), None

    (x, kpool, vpool), _ = jax.lax.scan(
        body, (x, pools["k"], pools["v"]),
        (jnp.arange(cfg.n_layers), params["layers"]))
    x = layers.norm_apply(params["final_norm"], x, cfg.norm_eps)
    last = x[jnp.arange(n), jnp.maximum(q_lens - 1, 0)]     # (N,D)
    logits = lm_logits(params, cfg, last)[..., :cfg.vocab_size]
    if seq_ids is None:
        rng, sub = jax.random.split(rng)
    else:
        # kv_lens == full prompt length == index of the token sampled
        sub = fold_row_keys(rng, seq_ids, kv_lens)
    first = sample_per_row(sub, logits, temperatures, top_k, top_p)
    return first, {"k": kpool, "v": vpool}, rng


@functools.partial(jax.jit, static_argnames=("cfg", "page_size"),
                   donate_argnames=("pools", "rng"))
def prefill_shared_paged(params, pools, tokens, q_lens, q_starts,
                         write_from, tables, rng, temperatures,
                         top_k=None, top_p=None, seq_ids=None,
                         *, cfg: ModelConfig, page_size: int):
    """Jitted single-device entry point over :func:`_prefill_shared_impl`
    (see its docstring for the full contract).  The tensor-parallel twin
    lives in ``repro.serve.tp`` and wraps the same impl in shard_map."""
    _count_trace("prefill_shared_paged")
    return _prefill_shared_impl(
        params, pools, tokens, q_lens, q_starts, write_from, tables, rng,
        temperatures, top_k, top_p, seq_ids, cfg=cfg, page_size=page_size)


def _prefill_chunk_impl(params, pools, tokens, q_lens, q_starts, tables,
                        *, cfg: ModelConfig, page_size: int,
                        psum_attn=None, psum_mlp=None):
    """One INTERMEDIATE chunk of a streaming prefill: KV only, no logits.

    The chunked-prefill twin of :func:`prefill_shared_paged`: row i runs
    the transformer over ``prompt[q_starts[i] : q_starts[i]+q_lens[i]]``,
    attending through the block tables (so chunk queries see every
    earlier chunk's KV in the pools), and scatters the chunk's KV at its
    absolute positions.  Because an intermediate chunk emits no token it
    computes NO final norm, NO logits, and — critically — consumes NO
    PRNG: the engine's rng key advances exactly as many times under
    chunked prefill as under one-shot prefill, which is what makes
    chunked/one-shot token streams identical even for sampled requests.

    Positions below ``q_starts`` are never written (they belong to
    earlier chunks or to shared prefix pages), so interleaving chunks
    with decode steps can only append — a 2k-token prompt stops costing
    one giant padded forward that stalls every running row.

    Returns ``new_pools`` only; ``pools`` is donated.  Retraces per
    (N, T, maxp) bucket like the other prefill entry points — chunk
    sizes are engine-fixed, so the bucket set stays O(log) small.
    ``psum_attn``/``psum_mlp`` are the TP reduction hooks (see
    :func:`_prefill_shared_impl`).
    """
    n, t = tokens.shape
    maxp = tables.shape[1]
    n_flat = pools["k"].shape[0]
    n_pages = n_flat // cfg.n_layers
    pos = q_starts[:, None] + jnp.arange(t)[None, :]        # (N,T) absolute
    qvalid = jnp.arange(t)[None, :] < q_lens[:, None]
    kv_lens = q_starts + q_lens                  # tokens in cache after us
    vpage = jnp.minimum(pos // page_size, maxp - 1)
    off = pos % page_size
    ppage = jnp.take_along_axis(tables, vpage, axis=1)      # (N,T)
    wvalid = qvalid & (ppage >= 0)
    kpos = jnp.arange(maxp * page_size)[None]               # (1,S)
    page_ok = jnp.repeat(tables >= 0, page_size, axis=1)    # (N,S)
    kv_ok = (kpos < kv_lens[:, None]) & page_ok             # (N,S)
    mask = kv_ok[:, None, :] & (kpos[:, None, :] <= pos[:, :, None])

    x = layers.embed_lookup(params["embed"], tokens)        # (N,T,D)

    def body(carry, inp):
        x, kp, vp = carry
        li, lp = inp
        base = li * n_pages
        h = layers.norm_apply(lp["norm1"], x, cfg.norm_eps)
        q, k, v = attention.qkv_proj(lp["attn"], cfg, h)
        if cfg.pos_embed == "rope":
            q = layers.apply_rope(q, pos, cfg.rope_theta)
            k = layers.apply_rope(k, pos, cfg.rope_theta)
        drop_page = jnp.where(wvalid, base + ppage, n_flat)
        kp = kp.at[drop_page, :, off].set(k.astype(kp.dtype), mode="drop")
        vp = vp.at[drop_page, :, off].set(v.astype(vp.dtype), mode="drop")
        att = _attend_pages(q, kp, vp, tables, base, mask, cfg=cfg)
        o = attention.out_proj(lp["attn"], cfg, att.astype(x.dtype))
        if psum_attn is not None:
            o = psum_attn(o)
        x = x + o
        h = layers.norm_apply(lp["norm2"], x, cfg.norm_eps)
        if _is_moe_layer(cfg):
            out, _ = moe.moe_apply(lp["ffn"], cfg, h)
        else:
            out = mlp.mlp_apply(lp["ffn"], cfg, h)
        if psum_mlp is not None:
            out = psum_mlp(out)
        return (x + out, kp, vp), None

    (_, kpool, vpool), _ = jax.lax.scan(
        body, (x, pools["k"], pools["v"]),
        (jnp.arange(cfg.n_layers), params["layers"]))
    return {"k": kpool, "v": vpool}


@functools.partial(jax.jit, static_argnames=("cfg", "page_size"),
                   donate_argnames=("pools",))
def prefill_chunk_paged(params, pools, tokens, q_lens, q_starts, tables,
                        *, cfg: ModelConfig, page_size: int):
    """Jitted single-device entry point over :func:`_prefill_chunk_impl`."""
    _count_trace("prefill_chunk_paged")
    return _prefill_chunk_impl(params, pools, tokens, q_lens, q_starts,
                               tables, cfg=cfg, page_size=page_size)


def _decode_logits_impl(params, pools, tables, lens, last_tokens, *,
                        cfg: ModelConfig, page_size: int,
                        use_pallas: Optional[bool] = None,
                        pages_per_block: Optional[int] = None,
                        psum_attn=None, psum_mlp=None):
    """The model half of a decode step: append each live row's KV at
    position ``lens`` and return ``(logits (B, vocab) float32,
    new_pools)``.  ``use_pallas=None`` lets the platform choose the
    attention kernel (see :mod:`repro.kernels`)."""
    maxp = tables.shape[1]
    n_flat = pools["k"].shape[0]
    n_pages = n_flat // cfg.n_layers
    x = layers.embed_lookup(params["embed"], last_tokens[:, None])
    pos = lens                                        # 0-based new position
    vpage = jnp.minimum(pos // page_size, maxp - 1)
    off = pos % page_size
    ppage = jnp.take_along_axis(tables, vpage[:, None], axis=1)[:, 0]
    active = ppage >= 0
    kv_lens = jnp.where(active, lens + 1, 0)

    def body(carry, inp):
        x, kp, vp = carry
        li, lp = inp
        base = li * n_pages
        h = layers.norm_apply(lp["norm1"], x, cfg.norm_eps)
        q, k, v = attention.qkv_proj(lp["attn"], cfg, h)
        if cfg.pos_embed == "rope":
            q = layers.apply_rope(q, pos[:, None], cfg.rope_theta)
            k = layers.apply_rope(k, pos[:, None], cfg.rope_theta)
        knew = k[:, 0].astype(kp.dtype)               # (B,K,hd)
        vnew = v[:, 0].astype(vp.dtype)
        kp = _append_kv(kp, base + ppage, off, knew, active)
        vp = _append_kv(vp, base + ppage, off, vnew, active)
        ltab = jnp.where(tables >= 0, tables + base, -1)
        att = paged_decode(q[:, 0], kp, vp, ltab, kv_lens,
                           use_pallas=use_pallas,
                           pages_per_block=pages_per_block)
        o = attention.out_proj(lp["attn"], cfg, att[:, None])
        if psum_attn is not None:
            o = psum_attn(o)
        x = x + o
        h = layers.norm_apply(lp["norm2"], x, cfg.norm_eps)
        if _is_moe_layer(cfg):
            out, _ = moe.moe_apply(lp["ffn"], cfg, h)
        else:
            out = mlp.mlp_apply(lp["ffn"], cfg, h)
        if psum_mlp is not None:
            out = psum_mlp(out)
        return (x + out, kp, vp), None

    (x, kpool, vpool), _ = jax.lax.scan(
        body, (x, pools["k"], pools["v"]),
        (jnp.arange(cfg.n_layers), params["layers"]))
    x = layers.norm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = lm_logits(params, cfg, x)[:, 0][..., :cfg.vocab_size]
    return logits, {"k": kpool, "v": vpool}


def _decode_step_impl(params, pools, tables, lens, last_tokens, rng,
                      temperatures, top_k=None, top_p=None, seq_ids=None,
                      *, cfg: ModelConfig, page_size: int,
                      use_pallas: Optional[bool] = None,
                      pages_per_block: Optional[int] = None,
                      psum_attn=None, psum_mlp=None):
    """One fused decode step for the whole running batch.

    last_tokens (B,) int32 — last sampled token per row;
    lens (B,) int32       — tokens already in cache (new token position);
    tables (B, maxp)      — MMU block tables (row of -1s = inactive slot);
    temperatures (B,)     — per-row sampling temperature (<= 0 = greedy);
    top_k (B,) int32      — optional per-row top-k filter (0 = disabled);
    top_p (B,) float32    — optional per-row nucleus filter (>=1 = off).

    Returns (next_tokens (B,) int32, new_pools, new_lens, new_rng).
    ``pools``, ``lens``, ``last_tokens`` and ``rng`` are donated: the
    flat KV pools are an aliased carry of the layer scan, updated in
    place.  ``tables`` is NOT donated — it is the MMU's cached device
    view, reused across steps.  The only host<->device traffic a caller
    needs per step is reading back the (B,) token vector.

    ``psum_attn``/``psum_mlp`` are the TP reduction hooks (see
    :func:`_prefill_shared_impl`): under ``repro.serve.tp`` this body
    runs inside shard_map with a per-device head/hidden slice of the
    weights and KV pools, and the hooks all-reduce the out-proj and FFN
    partial sums over the ``model`` axis.
    """
    logits, pools = _decode_logits_impl(
        params, pools, tables, lens, last_tokens, cfg=cfg,
        page_size=page_size, use_pallas=use_pallas,
        pages_per_block=pages_per_block, psum_attn=psum_attn,
        psum_mlp=psum_mlp)
    if seq_ids is None:
        rng, sub = jax.random.split(rng)
    else:
        # lens + 1 == index of the token being sampled: counter-based
        # keys make the draw independent of batching/step interleave
        sub = fold_row_keys(rng, seq_ids, lens + 1)
    # sample every row (the host ignores empty slots): a live row whose
    # write-position page was evicted still emits a real (degraded)
    # sample, matching the host-side oracle's behaviour under pressure.
    next_tokens = sample_per_row(sub, logits, temperatures, top_k, top_p)
    # lens mirrors the host's per-step append unconditionally, so an
    # evicted row's write position keeps tracking host truth and the row
    # self-reactivates once its next page is mapped (slot transitions
    # reset the counters host-side).
    new_lens = lens + 1
    return next_tokens, pools, new_lens, rng


@functools.partial(jax.jit, static_argnames=("cfg", "page_size",
                                             "use_pallas",
                                             "pages_per_block"),
                   donate_argnames=("pools", "lens", "last_tokens", "rng"))
def decode_step_paged(params, pools, tables, lens, last_tokens, rng,
                      temperatures, top_k=None, top_p=None, seq_ids=None,
                      *, cfg: ModelConfig, page_size: int,
                      use_pallas: Optional[bool] = None,
                      pages_per_block: Optional[int] = None):
    """Jitted single-device entry point over :func:`_decode_step_impl`
    (see its docstring for the full contract).  The tensor-parallel twin
    lives in ``repro.serve.tp``."""
    _count_trace("decode_step_paged")
    return _decode_step_impl(
        params, pools, tables, lens, last_tokens, rng, temperatures,
        top_k, top_p, seq_ids, cfg=cfg, page_size=page_size,
        use_pallas=use_pallas, pages_per_block=pages_per_block)


@functools.partial(jax.jit, static_argnames=("cfg", "page_size",
                                             "use_pallas",
                                             "pages_per_block"))
def decode_logits_paged(params, pools, tables, lens, last_tokens, *,
                        cfg: ModelConfig, page_size: int,
                        use_pallas: Optional[bool] = None,
                        pages_per_block: Optional[int] = None):
    """Logit-level twin of :func:`decode_step_paged` for parity checks:
    same model half, no sampling, nothing donated.  Returns
    ``(logits (B, vocab) float32, new_pools)``, so two kernel paths can
    be compared on one pool state."""
    return _decode_logits_impl(
        params, pools, tables, lens, last_tokens, cfg=cfg,
        page_size=page_size, use_pallas=use_pallas,
        pages_per_block=pages_per_block)
