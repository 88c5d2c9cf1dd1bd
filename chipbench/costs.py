"""Operations and bytes of the served model, from a configuration's sizes.

The counts are of the work the algorithm needs, whatever implements it:
matmul FLOPs count 2 per multiply-add, attention counts the q.k and p.v
products over the keys a query really sees (its causal context), and
the paged-attention bytes are the live tokens' K and V at the
configuration's stated dtype (bfloat16) plus q and o.
"""
from __future__ import annotations

import json
from pathlib import Path

from chipbench.model import Dims

STATED_BYTES = 2                     # bfloat16, as the configurations state


def linear_flops(d: Dims) -> int:
    """Matmul FLOPs of the layers for one token (no head, no attention)."""
    per_layer = (d.d * d.h * d.hd + 2 * d.d * d.kv * d.hd + d.h * d.hd * d.d
                 + 3 * d.d * d.ff)
    return 2 * d.layers * per_layer


def head_flops(d: Dims) -> int:
    """FLOPs of the output head for one token whose logits are computed."""
    return 2 * d.d * d.vocab


def attn_flops(d: Dims, ctx: int) -> int:
    """Attention FLOPs of one query over ``ctx`` keys, all layers."""
    return 4 * d.layers * d.h * d.hd * ctx


def prefill_attn_flops(d: Dims, start: int, n: int) -> int:
    """Attention FLOPs of queries at positions ``start .. start+n-1``,
    each over its causal context (position + 1 keys)."""
    ctx_sum = n * start + n * (n + 1) // 2
    return attn_flops(d, ctx_sum)


def kernel_bytes(d: Dims, ctx_sum: int, rows: int) -> int:
    """Paged decode attention bytes: K and V of ``ctx_sum`` live tokens,
    plus q and o of ``rows`` rows, all layers, at the stated dtype."""
    kv = 2 * ctx_sum * d.kv * d.hd
    qo = 2 * rows * d.h * d.hd
    return d.layers * (kv + qo) * STATED_BYTES


def peaks(device_kind: str, path: Path | None = None) -> dict:
    """The chip's published peaks; an unknown device is an error."""
    path = path or Path(__file__).with_name("peaks.json")
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path.name}; add them with their source")
    return table[device_kind]
