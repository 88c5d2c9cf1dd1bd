"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel package ships <name>.py (pl.pallas_call + BlockSpec VMEM
tiling), ops.py (jit'd dispatch wrapper) and ref.py (pure-jnp oracle).

The platform chooses the kernel mode, here and nowhere else: on a TPU the
kernels compile with Mosaic and the serving engine uses them by default;
on any other backend they run in Pallas interpret mode (tests) and the
engine defaults to the XLA reference path.  Callers may still pass an
explicit ``interpret=`` / ``use_pallas=``.
"""
from __future__ import annotations

from typing import Optional

import jax


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU (kernels compile)."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``interpret`` as given, else interpret mode exactly off the TPU."""
    return (not on_tpu()) if interpret is None else bool(interpret)


def resolve_use_pallas(use_pallas: Optional[bool]) -> bool:
    """``use_pallas`` as given, else the compiled kernel on the TPU and
    the XLA reference elsewhere."""
    return on_tpu() if use_pallas is None else bool(use_pallas)
