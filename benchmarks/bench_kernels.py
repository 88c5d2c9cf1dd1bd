"""Kernel microbenchmarks: paged-attention decode, Pallas vs jnp oracle.

One row per (batch, pages-per-seq, kernel, pages_per_block) cell; rows
carry a ``config`` key and a tokens/s figure so the suite lands in the
machine-readable ``BENCH_kernels.json`` artifact and can be diffed across
PRs by ``scripts/diff_bench.py``.
"""
from __future__ import annotations

from typing import Dict, List

from benchmarks.common import timeit

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention.ops import paged_decode

HEADS, KV_HEADS, HEAD_DIM = 8, 4, 64
PAGE = 16


def _cell(b: int, seq_pages: int, kern: str,
          ppb: int | None) -> Dict[str, float]:
    rng = np.random.RandomState(b * 131 + seq_pages)
    n_pages = b * seq_pages + 8
    q = jnp.asarray(rng.randn(b, HEADS, HEAD_DIM), jnp.float32)
    kp = jnp.asarray(rng.randn(n_pages, KV_HEADS, PAGE, HEAD_DIM) * 0.3,
                     jnp.float32)
    vp = jnp.asarray(rng.randn(n_pages, KV_HEADS, PAGE, HEAD_DIM) * 0.3,
                     jnp.float32)
    tables = jnp.asarray(
        rng.permutation(n_pages)[:b * seq_pages].reshape(b, seq_pages)
        .astype(np.int32))
    lens = jnp.full((b,), seq_pages * PAGE, jnp.int32)
    use_pallas = kern == "pallas"

    def step():
        paged_decode(q, kp, vp, tables, lens, use_pallas=use_pallas,
                     pages_per_block=ppb).block_until_ready()

    t = timeit(step, warmup=2, trials=5)
    ppb_tag = f"-ppb{ppb}" if ppb is not None else ""
    return {
        "config": f"b{b}-p{seq_pages}-{kern}{ppb_tag}",
        "batch": b,
        "seq_pages": seq_pages,
        "kernel": kern,
        # best-of-trials: the gated trend metric must be robust to the
        # dispatch/GC spikes that give the interpret-mode pallas cells
        # std ~ mean (mean-based tokens/s swung >2x run-to-run, which no
        # sane CI floor survives; min-of-5 is stable)
        "tokens_per_s": b / max(t["min_s"], 1e-12),
        "mean_s": t["mean_s"],
        "std_s": t["std_s"],
        "min_s": t["min_s"],
    }


def run() -> List[Dict[str, float]]:
    rows = []
    for b in (4, 8):
        for seq_pages in (4, 8):
            rows.append(_cell(b, seq_pages, "ref", None))
            rows.append(_cell(b, seq_pages, "pallas", None))
            rows.append(_cell(b, seq_pages, "pallas", 2))
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit
    emit(run(), "Kernel microbench: paged attention ref vs pallas")
