"""Pallas TPU flash-attention backward kernels.

Standard two-kernel decomposition with the forward's logsumexp residual:

  * ``_dq_kernel``  — grid (b, h, q_blocks, k_blocks), k sequential:
                      dq += (p ∘ (dp − D)) @ k · scale, dq in VMEM scratch;
  * ``_dkv_kernel`` — grid (b, kv_head, k_blocks, q_blocks), q sequential:
                      dk += (pᵀ ∘ (dp − D)ᵀ) @ q · scale, dv += pᵀ @ do,
                      GQA accumulated by looping the group's q heads in-block;

where p = exp(q kᵀ·scale − lse) and D = rowsum(do ∘ o) (computed inline).
The forward (``flash_attention.py``) is extended to emit lse.  All
accumulation fp32.  ``ops.mha_vjp`` wires fwd+bwd into a jax.custom_vjp;
tests sweep against jax.grad of the jnp oracle.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _mask(s, q_start, k_start, block_q, block_k, seq_len, causal, window):
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                              (block_q, block_k), 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                              (block_q, block_k), 1)
    m = kpos < seq_len
    if causal:
        m = jnp.logical_and(m, kpos <= qpos)
    if window > 0:
        m = jnp.logical_and(m, kpos > qpos - window)
    return jnp.where(m, s, NEG_INF)


# ================================================================== dq =====
def _dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref,
               acc, *, sm_scale, causal, window, block_q, block_k, seq_len):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    q_start = qi * block_q
    k_start = ki * block_k

    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        o = o_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0].astype(jnp.float32)          # (bq,)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = _mask(s * sm_scale, q_start, k_start, block_q, block_k,
                  seq_len, causal, window)
        p = jnp.exp(s - lse[:, None])                    # (bq, bk)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        dcap = jnp.sum(do * o, axis=1, keepdims=True)    # D (bq,1)
        ds = p * (dp - dcap)
        acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale

    conds = []
    if causal:
        conds.append(k_start <= q_start + block_q - 1)
    if window > 0:
        conds.append(k_start + block_k - 1 > q_start - window)
    if conds:
        pl.when(functools.reduce(jnp.logical_and, conds))(_body)
    else:
        _body()

    @pl.when(ki == nk - 1)
    def _done():
        dq_ref[0, 0] = acc[...].astype(dq_ref.dtype)


# ================================================================= dkv =====
def _dkv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *,
                sm_scale, causal, window, block_q, block_k, seq_len,
                group):
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start = qi * block_q
    k_start = ki * block_k

    def _body():
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        for gi in range(group):                          # q heads of group
            q = q_ref[0, 0, gi].astype(jnp.float32)      # (bq, d)
            o = o_ref[0, 0, gi].astype(jnp.float32)
            do = do_ref[0, 0, gi].astype(jnp.float32)
            lse = lse_ref[0, 0, gi].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = _mask(s * sm_scale, q_start, k_start, block_q, block_k,
                      seq_len, causal, window)
            p = jnp.exp(s - lse[:, None])                # (bq, bk)
            dv_acc[...] += jax.lax.dot_general(
                p, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)      # (bk, d)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            dcap = jnp.sum(do * o, axis=1, keepdims=True)
            ds = p * (dp - dcap)                         # (bq, bk)
            dk_acc[...] += jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale

    conds = []
    if causal:
        conds.append(k_start <= q_start + block_q - 1)
    if window > 0:
        conds.append(k_start + block_k - 1 > q_start - window)
    if conds:
        pl.when(functools.reduce(jnp.logical_and, conds))(_body)
    else:
        _body()

    @pl.when(qi == nq - 1)
    def _done():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


# ============================================================== wrappers ====
def _pad_seq(x, block, axis=2):
    pad = (-x.shape[axis]) % block
    if pad:
        cfg = [(0, 0)] * x.ndim
        cfg[axis] = (0, pad)
        x = jnp.pad(x, cfg)
    return x


def flash_attention_bwd(q, k, v, o, do, lse, *, causal=True, window=0,
                        sm_scale: Optional[float] = None,
                        block_q: int = 128, block_k: int = 128,
                        interpret: Optional[bool] = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """q/o/do (B,H,Sq,D); k/v (B,K,Sk,D); lse (B,H,Sq) -> (dq, dk, dv)."""
    b, h, sq, d = q.shape
    kh, sk = k.shape[1], k.shape[2]
    group = h // kh
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    block_q = min(block_q, max(sq, 8))
    block_k = min(block_k, max(sk, 8))

    q_, o_, do_ = (_pad_seq(x, block_q) for x in (q, o, do))
    lse_ = _pad_seq(lse[..., None], block_q)[..., 0] + 0.0
    k_, v_ = (_pad_seq(x, block_k) for x in (k, v))
    nq = q_.shape[2] // block_q
    nk = k_.shape[2] // block_k

    scr = [pltpu.VMEM((block_q, d), jnp.float32)]
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, causal=causal,
                          window=window, block_q=block_q, block_k=block_k,
                          seq_len=sk),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q),
                         lambda bi, hi, qi, ki: (bi, hi, qi)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q_.shape, q.dtype),
        scratch_shapes=scr,
        interpret=resolve_interpret(interpret),
    )(q_, k_, v_, o_, do_, lse_)[:, :, :sq]

    # q-side tensors grouped per kv head for the dkv kernel
    qg = q_.reshape(b, kh, group, q_.shape[2], d)
    og = o_.reshape(b, kh, group, q_.shape[2], d)
    dog = do_.reshape(b, kh, group, q_.shape[2], d)
    lseg = lse_.reshape(b, kh, group, q_.shape[2])

    scr2 = [pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32)]
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          window=window, block_q=block_q, block_k=block_k,
                          seq_len=sk, group=group),
        grid=(b, kh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, group, block_q, d),
                         lambda bi, hi, ki, qi: (bi, hi, 0, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, group, block_q, d),
                         lambda bi, hi, ki, qi: (bi, hi, 0, qi, 0)),
            pl.BlockSpec((1, 1, group, block_q, d),
                         lambda bi, hi, ki, qi: (bi, hi, 0, qi, 0)),
            pl.BlockSpec((1, 1, group, block_q),
                         lambda bi, hi, ki, qi: (bi, hi, 0, qi)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct(k_.shape, k.dtype),
                   jax.ShapeDtypeStruct(v_.shape, v.dtype)],
        scratch_shapes=scr2,
        interpret=resolve_interpret(interpret),
    )(qg, k_, v_, og, dog, lseg)
    return dq, dk[:, :, :sk], dv[:, :, :sk]
