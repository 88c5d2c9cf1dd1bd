#!/usr/bin/env python3
"""Chip benchmark of the serving path: one run of one cell.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout.  The cells, metrics and configurations
are named in ``BENCHMARK.json``; ``PERF.md`` says what each one is for.
The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and, traced,
``breakdown``).  Without a TPU it exits non-zero and prints no result;
``--rehearse`` runs the same code on the CPU at the reduced sizes.
"""
import time

T0 = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:], t0=T0))
