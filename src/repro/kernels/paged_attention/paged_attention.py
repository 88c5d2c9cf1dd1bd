"""Pallas TPU paged-attention decode kernel — the MMU service's datapath.

This is the paper-technique kernel: decode attention that reads KV through
the MMU's page tables (the "TLB lookup" in hardware).  TPU adaptation:

  * KV lives in a head-major paged pool ``(n_pages, kv_heads, page_size,
    head_dim)`` (HBM); sequences own scattered page lists.  Head-major
    makes one (page, kv head) block a ``(page_size, head_dim)`` tile
    whose last two dims Mosaic can DMA at any head count (a token-major
    pool would need a 1-wide block on the head axis, which v5e refuses);
  * the grid is (batch, kv_heads, page_groups); the group axis is
    sequential, carrying the online-softmax state (m/l/acc) in VMEM
    scratch;
  * the block table arrives via ``PrefetchScalarGridSpec`` — it is consumed
    by the *index_map*, so the page fetch address is computed from SMEM
    before the DMA issues: that is precisely a hardware TLB walk,
    reshaped for the MXU;
  * ``pages_per_block`` pages are fetched per grid step (one BlockSpec per
    page in the group, since pages are scattered in the pool) and
    concatenated into a single (pages_per_block * page_size, d) KV tile,
    so small page sizes stop starving the MXU with tiny matmuls;
  * GQA: all ``group = H // KV`` query heads of one kv head are processed
    together as the (group, head_dim) q tile — KV is fetched once per page
    regardless of group size;
  * out-of-range pages (beyond seq_len) are masked, and invalid table
    entries (-1, e.g. host-swapped pages or empty batch slots) index
    page 0 but stay masked; a page group that is entirely masked
    contributes nothing (the online-softmax update is where-guarded).
    The page mask is built from the int32 table entries themselves, so
    no boolean vector is ever concatenated in the kernel.

Oracle: ``ref.py``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30
# Full float32 contractions: a decode q tile is only ``group`` rows, so the
# extra MXU passes are few, and they keep the kernel within float32
# rounding of the reference path at any default matmul precision.
_F32 = jax.lax.Precision.HIGHEST


def _pa_kernel(tables_ref, lens_ref,           # scalar prefetch (SMEM)
               q_ref, *refs, page_size: int, sm_scale: float,
               pages_per_block: int):
    ppb = pages_per_block
    k_refs = refs[:ppb]
    v_refs = refs[ppb:2 * ppb]
    o_ref = refs[2 * ppb]
    m_scratch, l_scratch, acc_scratch = refs[2 * ppb + 1:]

    b = pl.program_id(0)
    gi = pl.program_id(2)
    ng = pl.num_programs(2)

    @pl.when(gi == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    seq_len = lens_ref[b]
    start = gi * ppb * page_size

    @pl.when(start < seq_len)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)              # (group, d)
        k = jnp.concatenate(
            [k_refs[j][0, 0] for j in range(ppb)],
            axis=0).astype(jnp.float32)                  # (ppb*page, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=_F32,
            preferred_element_type=jnp.float32) * sm_scale  # (group, ppb*pg)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # table entry of each column's page, selected from SMEM scalars
        entry = jnp.full(s.shape, -1, jnp.int32)
        for j in range(ppb):
            entry = jnp.where(col // page_size == j,
                              tables_ref[b, gi * ppb + j], entry)
        s = jnp.where((start + col < seq_len) & (entry >= 0), s, NEG_INF)

        m_prev = m_scratch[...]                          # (group, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # a fully-masked group leaves m_new at NEG_INF: exp(s - m_new)
        # would be exp(0)=1 there, so zero the weights explicitly.
        p = jnp.where(m_new > NEG_INF / 2, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scratch[...] = alpha * l_scratch[...] + jnp.sum(
            p, axis=1, keepdims=True)
        v = jnp.concatenate(
            [v_refs[j][0, 0] for j in range(ppb)],
            axis=0).astype(jnp.float32)                  # (ppb*page, d)
        acc_scratch[...] = acc_scratch[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), precision=_F32,
            preferred_element_type=jnp.float32)
        m_scratch[...] = m_new

    @pl.when(gi == ng - 1)
    def _done():
        l = l_scratch[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scratch[...] / l).astype(o_ref.dtype)


def default_pages_per_block(page_size: int, max_pages: int,
                            target: int = 128) -> int:
    """Enough pages per grid step for a ~``target``-row KV tile."""
    return max(1, min(max_pages, -(-target // page_size)))


def paged_attention(q, k_pages, v_pages, block_tables, seq_lens, *,
                    sm_scale: Optional[float] = None,
                    pages_per_block: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Decode attention through page tables.

    q            (B, H, D)         — one new token per sequence
    k/v_pages    (P, K, page, D)   — the MMU's device page pool, head-major
    block_tables (B, max_pages)    int32 physical page ids (-1 = unmapped)
    seq_lens     (B,)              int32 valid tokens per sequence
    pages_per_block                pages fetched/processed per grid step
                                   (None = auto-size toward a 128-row tile)
    interpret                      None = interpret mode exactly off the TPU
    -> (B, H, D)
    """
    b, h, d = q.shape
    n_pages, kh, page_size, _ = k_pages.shape
    group = h // kh
    max_pages = block_tables.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if pages_per_block is None:
        pages_per_block = default_pages_per_block(page_size, max_pages)
    ppb = max(1, min(int(pages_per_block), max_pages))
    ng = -(-max_pages // ppb)
    if ng * ppb != max_pages:                # pad width to a group multiple
        pad = ng * ppb - max_pages
        block_tables = jnp.pad(block_tables, ((0, 0), (0, pad)),
                               constant_values=-1)

    # (B, K, group, D) query tile per (batch, kv head)
    qg = q.reshape(b, kh, group, d)

    kernel = functools.partial(_pa_kernel, page_size=page_size,
                               sm_scale=sm_scale, pages_per_block=ppb)

    def _page_spec(j):
        return pl.BlockSpec(
            (1, 1, page_size, d),
            lambda bi, ki, gi, tables, lens, j=j:
            (jnp.maximum(tables[bi, gi * ppb + j], 0), ki, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kh, ng),
        in_specs=(
            [pl.BlockSpec((1, 1, group, d),
                          lambda bi, ki, gi, tables, lens: (bi, ki, 0, 0))]
            + [_page_spec(j) for j in range(ppb)]          # k page group
            + [_page_spec(j) for j in range(ppb)]          # v page group
        ),
        out_specs=pl.BlockSpec((1, 1, group, d),
                               lambda bi, ki, gi, tables, lens:
                               (bi, ki, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, group, d), q.dtype),
        interpret=resolve_interpret(interpret),
    )(block_tables, seq_lens, qg,
      *([k_pages] * ppb), *([v_pages] * ppb))
    return out.reshape(b, h, d)
