"""Jitted public wrapper for paged decode attention."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels import resolve_use_pallas
from repro.kernels.paged_attention.paged_attention import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret",
                                             "pages_per_block"))
def paged_decode(q, k_pages, v_pages, block_tables, seq_lens, *,
                 use_pallas: Optional[bool] = None,
                 interpret: Optional[bool] = None,
                 pages_per_block=None):
    """q (B, H, D); pages (P, K, page, D); tables (B, maxp); lens (B,).

    ``use_pallas``/``interpret`` default to what the platform supports
    (see :mod:`repro.kernels`): the compiled kernel on a TPU, the
    jnp oracle elsewhere.  ``pages_per_block`` widens the Pallas grid
    step to process that many pages at once (None = auto-size toward a
    128-row KV tile)."""
    if resolve_use_pallas(use_pallas):
        return paged_attention(q, k_pages, v_pages, block_tables, seq_lens,
                               pages_per_block=pages_per_block,
                               interpret=interpret)
    return paged_attention_ref(q, k_pages, v_pages, block_tables, seq_lens)
