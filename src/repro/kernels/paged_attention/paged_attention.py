"""Pallas TPU paged-attention decode kernel — the MMU service's datapath.

This is the paper-technique kernel: decode attention that reads KV through
the MMU's page tables (the "TLB lookup" in hardware).  TPU adaptation:

  * KV lives in a head-major paged pool ``(n_pages, kv_heads, page_size,
    head_dim)`` (HBM); sequences own scattered page lists.  All kv heads
    of one page are contiguous, so one ``(1, kv_heads, page_size,
    head_dim)`` block is a single DMA per page for every head, and its
    last two dims are a ``(page_size, head_dim)`` tile Mosaic accepts at
    any head count;
  * the grid is one flat axis of the live (row, page group) steps only:
    the wrapper sizes it from ``seq_lens`` on the device (a dynamic grid)
    and lays out, per step, its row and group as two more scalar-prefetch
    vectors, rows in order and every row at least once (an empty row's one
    step writes its zero output).  A row's groups are consecutive steps,
    carrying the online-softmax state (m/l/acc, one row per query head) in
    VMEM scratch; its q and o blocks stay resident across them.  Every kv
    head of a row is computed in one step with head-batched contractions.
    Work follows the live pages, not the table width: no grid step lies
    past a row's last live group;
  * the block table and the lengths arrive via ``PrefetchScalarGridSpec``
    — they are consumed by the *index_map*, so the page fetch address is
    computed from SMEM before the DMA issues: that is precisely a
    hardware TLB walk, reshaped for the MXU;
  * ``pages_per_block`` pages are fetched per grid step (one BlockSpec per
    page slot of the group, since pages are scattered in the pool) and
    concatenated into a single (kv_heads, pages_per_block * page_size, d)
    KV tile, so small page sizes stop starving the MXU with tiny matmuls;
  * in a row's last group the index map clamps a page slot past the last
    live page to the slot's previous group: its block index repeats, so
    Pallas issues no DMA.  A slot that is never live (a row shorter than
    one group) reads the row's last live page instead, so no table entry
    past a row's length is ever fetched; an empty row (length 0) fetches
    page 0 once per slot and computes nothing;
  * GQA: all ``group = H // KV`` query heads of one kv head are processed
    together as the (group, head_dim) q tile of that head — KV is fetched
    once per page regardless of group size;
  * out-of-range positions (beyond seq_len) are masked, and invalid table
    entries (-1, e.g. host-swapped pages inside the live range) index
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


def _pa_kernel(tables_ref, lens_ref, rows_ref, groups_ref,  # SMEM
               q_ref, *refs, page_size: int, sm_scale: float,
               pages_per_block: int, max_groups: int):
    ppb = pages_per_block
    k_refs = refs[:ppb]
    v_refs = refs[ppb:2 * ppb]
    o_ref = refs[2 * ppb]
    m_scratch, l_scratch, acc_scratch = refs[2 * ppb + 1:]

    step = pl.program_id(0)
    b = rows_ref[step]
    gi = groups_ref[step]

    @pl.when(gi == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    seq_len = lens_ref[b]
    start = gi * ppb * page_size

    @pl.when(start < seq_len)
    def _body():
        q = q_ref[0].astype(jnp.float32)                 # (K, group, d)
        k = jnp.concatenate(
            [k_refs[j][0] for j in range(ppb)],
            axis=1).astype(jnp.float32)                  # (K, ppb*page, d)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))), precision=_F32,
            preferred_element_type=jnp.float32) * sm_scale  # (K, group, cols)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        # table entry of each column's page, selected from SMEM scalars
        entry = jnp.full(s.shape, -1, jnp.int32)
        for j in range(ppb):
            entry = jnp.where(col // page_size == j,
                              tables_ref[b, gi * ppb + j], entry)
        s = jnp.where((start + col < seq_len) & (entry >= 0), s, NEG_INF)

        m_prev = m_scratch[...]                          # (K, group, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        # a fully-masked group leaves m_new at NEG_INF: exp(s - m_new)
        # would be exp(0)=1 there, so zero the weights explicitly.
        p = jnp.where(m_new > NEG_INF / 2, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scratch[...] = alpha * l_scratch[...] + jnp.sum(
            p, axis=2, keepdims=True)
        v = jnp.concatenate(
            [v_refs[j][0] for j in range(ppb)],
            axis=1).astype(jnp.float32)                  # (K, ppb*page, d)
        acc_scratch[...] = acc_scratch[...] * alpha + jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))), precision=_F32,
            preferred_element_type=jnp.float32)
        m_scratch[...] = m_new

    @pl.when(gi == _n_groups(seq_len, ppb * page_size, max_groups) - 1)
    def _done():
        l = l_scratch[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scratch[...] / l).astype(o_ref.dtype)


def _n_groups(seq_len, span: int, max_groups: int):
    """Page groups a row of ``seq_len`` tokens spans, within the table;
    an empty row one."""
    return jnp.clip((seq_len + span - 1) // span, 1, max_groups)


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

    # (B, K, group, D) query tile per row: every kv head in one block
    qg = q.reshape(b, kh, group, d)

    # the grid walks only the live page groups: step i works on group
    # groups[i] of row rows[i], rows in order, every row at least once
    # (an empty row's one step writes its zero output)
    n_groups = _n_groups(seq_lens, ppb * page_size, ng)
    ends = jnp.cumsum(n_groups)
    steps = jnp.arange(b * ng, dtype=jnp.int32)
    rows = jnp.minimum(
        jnp.sum(steps[:, None] >= ends[None, :], axis=1, dtype=jnp.int32),
        b - 1)
    groups = steps - (ends - n_groups)[rows]

    kernel = functools.partial(_pa_kernel, page_size=page_size,
                               sm_scale=sm_scale, pages_per_block=ppb,
                               max_groups=ng)

    def _page_spec(j):
        def index(i, tables, lens, rows, groups):
            # slot j of group g holds page g*ppb + j: in the row's last
            # group a slot past its last live page keeps the block of its
            # previous group (no DMA); a slot that is never live reads the
            # row's last live page, so no page past the length is fetched
            bi = rows[i]
            last_page = jnp.maximum(lens[bi] - 1, 0) // page_size
            g = jnp.minimum(groups[i], jnp.maximum(last_page - j, 0) // ppb)
            pos = jnp.where(last_page >= j, g * ppb + j, last_page)
            return (jnp.maximum(tables[bi, pos], 0), 0, 0, 0)
        return pl.BlockSpec((1, kh, page_size, d), index)

    def _row_spec():
        return pl.BlockSpec((1, kh, group, d),
                            lambda i, tables, lens, rows, groups:
                            (rows[i], 0, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(ends[-1],),
        in_specs=(
            [_row_spec()]
            + [_page_spec(j) for j in range(ppb)]          # k page group
            + [_page_spec(j) for j in range(ppb)]          # v page group
        ),
        out_specs=_row_spec(),
        scratch_shapes=[
            pltpu.VMEM((kh, group, 1), jnp.float32),
            pltpu.VMEM((kh, group, 1), jnp.float32),
            pltpu.VMEM((kh, group, d), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, group, d), q.dtype),
        interpret=resolve_interpret(interpret),
    )(block_tables, seq_lens, rows, groups, qg,
      *([k_pages] * ppb), *([v_pages] * ppb))
    return out.reshape(b, h, d)
