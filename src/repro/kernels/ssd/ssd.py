"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

The SSD recurrence (state-space duality, arXiv:2405.21060) splits the
sequence into chunks: within a chunk the output is an attention-like
(L x L)-masked matmul (MXU work); across chunks a tiny (head_dim x d_state)
state carries the recurrence.  TPU mapping:

  * grid = (batch, heads, n_chunks); the chunk axis is sequential, the
    (P x N) fp32 state lives in VMEM scratch between chunk steps — the
    recurrence never round-trips HBM;
  * each chunk step runs three MXU matmuls: C·Bᵀ (L x L scores), scores·x
    (diagonal term), Cₛ·state (off-diagonal term) and one xᵀ·B state update;
  * chunk length defaults to 256 and L, N, P are 128-multiples-friendly.

Inputs are pre-activation (dt already softplus'ed, A negative).  Grouped
B/C (G < H) is resolved in the index_map like GQA.  Oracle: ``ref.py``
(also the pure-jnp path used by the model).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_out_ref,
                state, *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    x = x_ref[0, :, 0].astype(jnp.float32)          # (L, P)
    dt = dt_ref[0, :, 0].astype(jnp.float32)        # (L,)
    a = a_ref[0]                                    # scalar A_h (negative)
    bm = b_ref[0, :, 0].astype(jnp.float32)         # (L, N)
    cm = c_ref[0, :, 0].astype(jnp.float32)         # (L, N)

    adt = dt * a                                    # (L,)
    cum = jnp.cumsum(adt)                           # (L,)
    seg = cum[-1]

    # ---- intra-chunk (diagonal) term --------------------------------------
    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (L,L)
    li = cum[:, None]
    lj = cum[None, :]
    mask = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.where(mask, jnp.exp(li - lj), 0.0) * dt[None, :]
    w = scores * decay                              # (L, L)
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)       # (L,P)

    # ---- inter-chunk (off-diagonal) term -----------------------------------
    c_scaled = cm * jnp.exp(cum)[:, None]           # (L, N)
    y = y + jax.lax.dot_general(c_scaled, state[...],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)   # (L,P)
    y_ref[0, :, 0] = y.astype(y_ref.dtype)

    # ---- state update -------------------------------------------------------
    dstate = jnp.exp(seg - cum) * dt                # (L,)
    xw = x * dstate[:, None]                        # (L, P)
    upd = jax.lax.dot_general(xw, bm, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)     # (P,N)
    state[...] = jnp.exp(seg) * state[...] + upd

    @pl.when(ci == nc - 1)
    def _done():
        st_out_ref[0, 0] = state[...]


def ssd_chunked_pallas(x, dt, A, Bm, C, *, chunk: int = 256,
                       interpret: Optional[bool] = None
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B,S,H,P); dt (B,S,H) post-softplus; A (H,); Bm/C (B,S,G,N).

    Returns (y (B,S,H,P), final_state (B,H,P,N) fp32).  S is padded to a
    chunk multiple (dt=0 padding is exact: zero dt means identity decay and
    zero input contribution)."""
    b, s_len, h, pd = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    rep = h // g
    pad = (-s_len) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    s_pad = x.shape[1]
    nc = s_pad // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    scratch = [pltpu.VMEM((pd, n), jnp.float32)]

    y, st = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, pd),
                         lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, chunk, 1),
                         lambda bi, hi, ci: (bi, ci, hi)),
            pl.BlockSpec((1,), lambda bi, hi, ci: (hi,)),
            pl.BlockSpec((1, chunk, 1, n),
                         lambda bi, hi, ci: (bi, ci, hi // rep, 0)),
            pl.BlockSpec((1, chunk, 1, n),
                         lambda bi, hi, ci: (bi, ci, hi // rep, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, pd),
                         lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, 1, pd, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s_pad, h, pd), x.dtype),
            jax.ShapeDtypeStruct((b, h, pd, n), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=resolve_interpret(interpret),
    )(x, dt, A, Bm, C)
    return y[:, :s_len], st
