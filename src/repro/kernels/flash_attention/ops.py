"""Jitted public wrapper for flash attention.

``mha(...)`` takes the model-layout tensors (B, S, H, D) and dispatches to
the Pallas kernel or the jnp oracle.  Both ``use_pallas`` and
``interpret`` default to what the platform supports (see
:mod:`repro.kernels`): the compiled kernel on a TPU, the jnp oracle
elsewhere.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_use_pallas
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref


@functools.partial(jax.jit, static_argnames=("causal", "window",
                                             "use_pallas", "interpret",
                                             "block_q", "block_k"))
def mha(q, k, v, *, causal: bool = True, window: int = 0,
        use_pallas: Optional[bool] = None,
        interpret: Optional[bool] = None,
        block_q: int = 128, block_k: int = 128):
    """q (B, Sq, H, D); k, v (B, Sk, K, D) -> (B, Sq, H, D)."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if resolve_use_pallas(use_pallas):
        ot = flash_attention(qt, kt, vt, causal=causal, window=window,
                             block_q=block_q, block_k=block_k,
                             interpret=interpret)
    else:
        ot = attention_ref(qt, kt, vt, causal=causal, window=window)
    return ot.transpose(0, 2, 1, 3)


# ------------------------------------------------------------- custom vjp --
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def mha_fused(q, k, v, causal: bool = True, window: int = 0,
              interpret: Optional[bool] = None):
    """Differentiable fused attention: Pallas fwd + Pallas bwd kernels.

    Layout (B, H, S, D).  Compiled on a TPU; interpret mode elsewhere
    (tests/test_kernels.py)."""
    return flash_attention(q, k, v, causal=causal, window=window,
                           interpret=interpret)


def _mha_fwd(q, k, v, causal, window, interpret):
    o, lse = flash_attention(q, k, v, causal=causal, window=window,
                             interpret=interpret, return_lse=True)
    return o, (q, k, v, o, lse)


def _mha_bwd(causal, window, interpret, res, do):
    from repro.kernels.flash_attention.flash_attention_bwd import (
        flash_attention_bwd)
    q, k, v, o, lse = res
    dq, dk, dv = flash_attention_bwd(q, k, v, o, do, lse, causal=causal,
                                     window=window, interpret=interpret)
    return dq, dk, dv


mha_fused.defvjp(_mha_fwd, _mha_bwd)
