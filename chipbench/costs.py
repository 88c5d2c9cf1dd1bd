"""What every architecture's counts share (``chipbench/archs/``), and
the chip's peaks.

The counts are of the work the algorithm needs, whatever implements it:
matmul FLOPs count 2 per multiply-add, attention counts the q.k and p.v
products over the keys a query really sees (its causal context), and
the paged-attention bytes are the live tokens' K and V at the
configuration's stated dtype (bfloat16) plus q and o.
"""
from __future__ import annotations

import json
from pathlib import Path

STATED_BYTES = 2                     # bfloat16, as the configurations state


def peaks(device_kind: str, path: Path | None = None) -> dict:
    """The chip's published peaks; an unknown device is an error."""
    path = path or Path(__file__).with_name("peaks.json")
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path.name}; add them with their source")
    return table[device_kind]
