"""Pallas TPU flash-attention forward kernel (training/prefill hot spot).

TPU adaptation of the blockwise-softmax algorithm:

  * grid = (batch, q_heads, q_blocks, k_blocks); the k axis is innermost and
    sequential ("arbitrary"), so the m/l/acc scratch carries across k blocks
    in VMEM — scores never round-trip to HBM;
  * BlockSpecs tile q/o as (block_q, head_dim) and k/v as (block_k,
    head_dim): head_dim is MXU-lane aligned (128) and the default 128/128
    tiles keep q+k+v+acc well under the ~16 MB v5e VMEM budget;
  * GQA happens in the index_map (kv head = q head // group) — repeated KV
    is never materialized;
  * causal / sliding-window tiles that are fully masked exit via pl.when
    without touching the MXU.

Accumulation is fp32 regardless of input dtype.  Oracle: ``ref.py``.
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


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
               m_scratch, l_scratch, acc_scratch, *,
               sm_scale: float, causal: bool, window: int,
               block_q: int, block_k: int, seq_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    q_start = qi * block_q
    k_start = ki * block_k

    def _body():
        q = q_ref[0, 0].astype(jnp.float32)              # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale

        qpos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = kpos < seq_len
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window > 0:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scratch[...]                          # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scratch[...] = alpha * l_scratch[...] + jnp.sum(
            p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)              # (bk, d)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scratch[...] = acc_scratch[...] * alpha + pv
        m_scratch[...] = m_new

    # block-level short-outs: skip fully-masked tiles entirely
    conds = []
    if causal:
        conds.append(k_start <= q_start + block_q - 1)
    if window > 0:
        conds.append(k_start + block_k - 1 > q_start - window)
    if conds:
        run = functools.reduce(jnp.logical_and, conds)
        pl.when(run)(_body)
    else:
        _body()

    @pl.when(ki == nk - 1)
    def _done():
        l = l_scratch[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scratch[...] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0, 0] = (m_scratch[..., 0]
                             + jnp.log(l[..., 0])).astype(lse_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    sm_scale: Optional[float] = None, block_q: int = 128,
                    block_k: int = 128, interpret: Optional[bool] = None,
                    return_lse: bool = False):
    """q (B, H, Sq, D); k, v (B, K, Sk, D) -> (B, H, Sq, D).

    H must be a multiple of K (GQA).  Sequence dims are padded to block
    multiples internally (masked out of the softmax)."""
    b, h, sq, d = q.shape
    kh, sk = k.shape[1], k.shape[2]
    assert h % kh == 0, f"GQA requires H % K == 0, got {h} % {kh}"
    group = h // kh
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)

    block_q = min(block_q, max(sq, 8))
    block_k = min(block_k, max(sk, 8))
    pq = (-sq) % block_q
    pk = (-sk) % block_k
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    nq = q.shape[2] // block_q
    nk = k.shape[2] // block_k

    if not return_lse:
        def kernel_nolse(q_ref, k_ref, v_ref, o_ref, m_s, l_s, a_s):
            _fa_kernel(q_ref, k_ref, v_ref, o_ref, None, m_s, l_s, a_s,
                       sm_scale=sm_scale, causal=causal, window=window,
                       block_q=block_q, block_k=block_k, seq_len=sk)
        kernel = kernel_nolse
        out_specs = pl.BlockSpec((1, 1, block_q, d),
                                 lambda bi, hi, qi, ki: (bi, hi, qi, 0))
        out_shape = jax.ShapeDtypeStruct((b, h, q.shape[2], d), q.dtype)
    else:
        kernel = functools.partial(
            _fa_kernel, sm_scale=sm_scale, causal=causal, window=window,
            block_q=block_q, block_k=block_k, seq_len=sk)
        out_specs = [
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q),
                         lambda bi, hi, qi, ki: (bi, hi, qi)),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((b, h, q.shape[2], d), q.dtype),
            jax.ShapeDtypeStruct((b, h, q.shape[2]), jnp.float32),
        ]

    scratch = [pltpu.VMEM((block_q, 1), jnp.float32),
               pltpu.VMEM((block_q, 1), jnp.float32),
               pltpu.VMEM((block_q, d), jnp.float32)]

    res = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=resolve_interpret(interpret),
    )(q, k, v)
    if return_lse:
        out, lse = res
        if pq:
            out, lse = out[:, :, :sq], lse[:, :, :sq]
        return out, lse
    out = res
    if pq:
        out = out[:, :, :sq]
    return out
