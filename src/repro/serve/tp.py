"""Tensor-parallel paged serving: shard_map twins of the decode hot path.

One serving tenant spans every device on a mesh's ``model`` axis while the
shell stays logically single — the Coyote v2 move of making placement a
property of the shell, not the app.  The engine keeps ONE MMU, ONE block
table, ONE refcounted prefix index and ONE pager; only the *tensors* are
partitioned:

  * **Weights** are Megatron-style tensor-parallel (``MeshRules.serving()``
    — TP columns, no FSDP rows, so decode never all-gathers weights):
    ``wq/wk/wv`` column-sharded on the flattened head dim, ``wo``
    row-sharded; SwiGLU ``w_gate/w_up`` column-sharded on ``d_ff``,
    ``w_down`` row-sharded.  Embeddings, norms, lm_head and MoE experts
    stay replicated.
  * **KV pools** shard axis 1 (``kv_heads``) on ``model``: each device
    holds EVERY page but only its head slice, so paged attention is
    collective-free (per-head softmax is device-local) and the page-id
    geometry — block tables, pager, migration wire format — is untouched.
  * **Reductions** go through :meth:`CollectiveService.all_reduce`
    (``axes=("model",)``): one psum after the attention out-projection and
    one after the FFN per layer.  Everything between blocks is replicated.
  * **Sampling** runs on replicated logits with a replicated PRNG key, so
    every device samples the same (B,) token vector and only that vector
    crosses to the host — the PR-2 device-resident carry invariant holds
    per shard.

Degradation is static and per-part: heads shard only when BOTH
``n_heads`` and ``n_kv_heads`` divide the TP degree (GQA grouping must
survive the split), the FFN only for non-MoE SwiGLU with divisible
``d_ff``.  A part that cannot shard is replicated and its psum is
skipped — never applied to an already-complete sum.

Validated on CPU via ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
(tests/test_mesh_serving.py, benchmarks/bench_multipod.py); the full guide
is docs/sharding.md.
"""
from __future__ import annotations

import functools
from dataclasses import replace
from typing import Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.services.collectives import CollectiveService
from repro.kernels import resolve_use_pallas
from repro.models.sharding import MeshRules
from repro.serve import paged_model


def tp_plan(cfg: ModelConfig, tp_size: int) -> Dict[str, bool]:
    """Static sharding decisions for a config at a TP degree.

    ``shard_heads``: attention weights + KV pools split on the head dim —
    requires whole query AND kv heads per shard (GQA groups must not
    straddle devices).  ``shard_mlp``: SwiGLU hidden dim split — MoE FFNs
    and GELU MLPs (whisper's ``b_down`` bias is applied inside the matmul
    epilogue, pre-reduction) stay replicated.
    """
    shard_heads = (tp_size > 1
                   and cfg.n_heads % tp_size == 0
                   and cfg.n_kv_heads % tp_size == 0)
    shard_mlp = (tp_size > 1 and cfg.moe is None and cfg.act == "silu"
                 and cfg.d_ff % tp_size == 0)
    return {"shard_heads": shard_heads, "shard_mlp": shard_mlp}


class TPContext:
    """Mesh-bound tensor-parallel twins of the paged serving kernels.

    Construct once per (engine, mesh); exposes placed parameters
    (``.params``), pool/state shardings, and jitted ``decode_step`` /
    ``prefill_shared`` / ``prefill_chunk`` callables with the same
    positional signatures as their single-device counterparts in
    :mod:`repro.serve.paged_model` (statics pre-bound).
    """

    def __init__(self, cfg: ModelConfig, mesh: Mesh, params, *,
                 page_size: int, use_pallas: Optional[bool] = None,
                 pages_per_block: Optional[int] = None,
                 collectives: Optional[CollectiveService] = None):
        self.cfg = cfg
        self.mesh = mesh
        self.rules = MeshRules.from_mesh(mesh).serving()
        self.axis = self.rules.tp_axis
        self.tp_size = self.rules.tp_size or 1
        self.collectives = (collectives if collectives is not None
                            else CollectiveService())
        plan = tp_plan(cfg, self.tp_size)
        self.shard_heads = plan["shard_heads"]
        self.shard_mlp = plan["shard_mlp"]
        # Per-device view of the model: the shard_map body sees LOCAL
        # head counts.  head_dim is pinned explicitly because
        # resolved_head_dim would otherwise re-derive from the reduced
        # n_heads (d_model // local_heads is wrong by a factor of tp).
        if self.shard_heads:
            self.local_cfg = replace(
                cfg, n_heads=cfg.n_heads // self.tp_size,
                n_kv_heads=cfg.n_kv_heads // self.tp_size,
                head_dim=cfg.resolved_head_dim)
        else:
            self.local_cfg = cfg
        self.replicated = NamedSharding(mesh, P())
        self.kv_spec = (P(None, self.axis, None, None) if self.shard_heads
                        else P())
        self.kv_sharding = NamedSharding(mesh, self.kv_spec)
        self._pspecs = self._param_specs(params)
        self.params = jax.device_put(
            params, jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 self._pspecs,
                                 is_leaf=lambda x: isinstance(x, P)))
        self._psum_attn = self._reduce if self.shard_heads else None
        self._psum_mlp = self._reduce if self.shard_mlp else None
        self.use_pallas = resolve_use_pallas(use_pallas)
        self.decode_step = self._build_decode(page_size, self.use_pallas,
                                              pages_per_block)
        self.prefill_shared = self._build_prefill_shared(page_size)
        self.prefill_chunk = self._build_prefill_chunk(page_size)

    # ------------------------------------------------------------ specs ----
    def _param_specs(self, params):
        """PartitionSpec pytree congruent with the serving param tree:
        replicated everywhere except the TP-sharded attention/FFN mats
        (stacked layer axis — index 0 — is never sharded)."""
        specs = jax.tree.map(lambda _: P(), params)
        ax = self.axis
        if self.shard_heads:
            a = specs["layers"]["attn"]
            a["wq"] = P(None, None, ax)
            a["wk"] = P(None, None, ax)
            a["wv"] = P(None, None, ax)
            a["wo"] = P(None, ax, None)
            for b in ("bq", "bk", "bv"):
                if b in a:
                    a[b] = P(None, ax)
        if self.shard_mlp:
            f = specs["layers"]["ffn"]
            f["w_gate"] = P(None, None, ax)
            f["w_up"] = P(None, None, ax)
            f["w_down"] = P(None, ax, None)
        return specs

    def _reduce(self, x):
        """Sum TP partials through the collective service port."""
        return self.collectives.all_reduce(x, self.mesh, axes=(self.axis,))

    # ----------------------------------------------------------- builders ----
    def _build_decode(self, page_size, use_pallas, pages_per_block):
        impl = functools.partial(
            paged_model._decode_step_impl, cfg=self.local_cfg,
            page_size=page_size, use_pallas=use_pallas,
            pages_per_block=pages_per_block,
            psum_attn=self._psum_attn, psum_mlp=self._psum_mlp)

        def local(params, pools, tables, lens, last, rng, temps, tk, tp_,
                  sids):
            paged_model._count_trace("decode_step_paged_tp")
            return impl(params, pools, tables, lens, last, rng, temps, tk,
                        tp_, sids)

        sm = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(self._pspecs, {"k": self.kv_spec, "v": self.kv_spec},
                      P(), P(), P(), P(), P(), P(), P(), P()),
            out_specs=(P(), {"k": self.kv_spec, "v": self.kv_spec},
                       P(), P()),
            check_vma=False)
        return jax.jit(sm, donate_argnums=(1, 3, 4, 5))

    def _build_prefill_shared(self, page_size):
        impl = functools.partial(
            paged_model._prefill_shared_impl, cfg=self.local_cfg,
            page_size=page_size, psum_attn=self._psum_attn,
            psum_mlp=self._psum_mlp)

        def local(params, pools, tokens, q_lens, q_starts, write_from,
                  tables, rng, temps, tk, tp_, sids):
            paged_model._count_trace("prefill_shared_paged_tp")
            return impl(params, pools, tokens, q_lens, q_starts,
                        write_from, tables, rng, temps, tk, tp_, sids)

        sm = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(self._pspecs, {"k": self.kv_spec, "v": self.kv_spec},
                      P(), P(), P(), P(), P(), P(), P(), P(), P(), P()),
            out_specs=(P(), {"k": self.kv_spec, "v": self.kv_spec}, P()),
            check_vma=False)
        return jax.jit(sm, donate_argnums=(1, 7))

    def _build_prefill_chunk(self, page_size):
        impl = functools.partial(
            paged_model._prefill_chunk_impl, cfg=self.local_cfg,
            page_size=page_size, psum_attn=self._psum_attn,
            psum_mlp=self._psum_mlp)

        def local(params, pools, tokens, q_lens, q_starts, tables):
            paged_model._count_trace("prefill_chunk_paged_tp")
            return impl(params, pools, tokens, q_lens, q_starts, tables)

        sm = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(self._pspecs, {"k": self.kv_spec, "v": self.kv_spec},
                      P(), P(), P(), P()),
            out_specs={"k": self.kv_spec, "v": self.kv_spec},
            check_vma=False)
        return jax.jit(sm, donate_argnums=(1,))

    # ------------------------------------------------------------- extras ----
    def prefill_paged(self, params, pools, tokens, lens, tables, rng,
                      temperatures, top_k=None, top_p=None):
        """TP twin of :func:`repro.serve.paged_model.prefill_paged`,
        routed through the shared-prefix kernel with zero coverage
        (q_starts = write_from = 0): full causal prefill over the paged
        KV with one batch-wide PRNG split, like the single-device
        original."""
        import jax.numpy as jnp
        n = tokens.shape[0]
        zeros = jnp.zeros((n,), jnp.int32)
        ones = (jnp.ones((n,), jnp.float32) if top_p is None else top_p)
        tk = jnp.zeros((n,), jnp.int32) if top_k is None else top_k
        return self.prefill_shared(params, pools, tokens, lens, zeros,
                                   zeros, tables, rng, temperatures, tk,
                                   ones, None)

    def allreduce_bytes_per_step(self, batch: int) -> int:
        """Modeled GLOBAL payload bytes all-reduced per decode step:
        one fp32 (B, 1, d_model) activation per enabled psum site per
        layer.  Feed to :meth:`CollectiveService.wire_bytes` for the
        per-device wire estimate (benchmarks/bench_multipod.py)."""
        sites = int(self.shard_heads) + int(self.shard_mlp)
        return sites * self.cfg.n_layers * batch * self.cfg.d_model * 4
