"""Jitted public wrapper for the SSD scan."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels import resolve_use_pallas
from repro.kernels.ssd.ref import ssd_chunked
from repro.kernels.ssd.ssd import ssd_chunked_pallas


@functools.partial(jax.jit, static_argnames=("chunk", "use_pallas",
                                             "interpret"))
def ssd(x, dt, A, Bm, C, *, chunk: int = 256,
        use_pallas: Optional[bool] = None,
        interpret: Optional[bool] = None):
    """Dispatch: Pallas kernel or chunked-jnp reference; both default to
    what the platform supports (see :mod:`repro.kernels`)."""
    if resolve_use_pallas(use_pallas):
        return ssd_chunked_pallas(x, dt, A, Bm, C, chunk=chunk,
                                  interpret=interpret)
    return ssd_chunked(x, dt, A, Bm, C, chunk=chunk)
