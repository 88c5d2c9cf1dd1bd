"""Prefill/decode disaggregation across the `pod` axis.

The paper's RDMA story at LLM scale: pod 0 runs compute-bound prefill,
pod 1 runs memory-bound decode, and the prefilled KV cache crosses the pod
boundary through the collective service's queue pairs — a one-sided
``rdma_write`` (collective_permute on the `pod` axis), exactly the
Coyote v2 networking service pattern (§6.2: the stack does "on-datapath
custom off-loads", here the off-load is the KV hand-off).

``make_handoff_fn`` builds the pjit-able transfer: inside shard_map over
the pod axis, the prefill pod sends its cache shard and the decode pod
receives it; intra-pod shardings (batch on data, seq on model) pass
through untouched, so the wire volume is exactly one cache copy over the
inter-pod links.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.services.collectives import CollectiveConfig, CollectiveService


def make_handoff_fn(mesh, svc: CollectiveService = None, *,
                    pod_axis: str = "pod"):
    """Returns handoff(cache_pytree) -> cache_pytree where every leaf has
    pod 0's data delivered to pod 1 (pod 0 keeps its copy: one-sided
    write semantics).  Leaves keep their intra-pod sharding."""
    svc = svc or CollectiveService(CollectiveConfig(pod_axis=pod_axis))
    qp = svc.create_qp(0, 1)
    n_pods = mesh.shape[pod_axis]
    assert n_pods >= 2, "disaggregation needs a multi-pod mesh"

    def _leaf_handoff(x):
        """x dim0 is pod-sharded: pod 0's rows = freshly prefilled KV,
        pod 1's rows = its decode pool.  After handoff, pod 1's rows hold
        pod 0's data (one-sided write); pod 0 keeps its copy."""
        def local(v):
            sent = svc.rdma_write(v, qp, pod_axis=pod_axis)
            idx = jax.lax.axis_index(pod_axis)
            return jnp.where(idx > 0, sent, v)
        return jax.shard_map(local, mesh=mesh,
                             in_specs=P(pod_axis),
                             out_specs=P(pod_axis),
                             check_vma=False)(x)

    def handoff(cache):
        return jax.tree.map(_leaf_handoff, cache)

    return handoff, qp


def handoff_wire_bytes(cache, n_pods: int = 2) -> float:
    """Modeled inter-pod bytes: one copy of the prefill pod's cache."""
    total = sum(x.nbytes for x in jax.tree.leaves(cache))
    return total / n_pods     # only the prefill pod's shard crosses
