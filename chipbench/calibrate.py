#!/usr/bin/env python3
"""Readings for the limit of ``correct``: runs one cell on many seeds in
one process (the programs compile once), each run with the float8
control judged in the program's place (``--control``: such a run must
read not correct) and the program's own gap read beside it.

    python3 chipbench/calibrate.py --workload <name> --seconds <s> \
        --seeds 1,2,3 [--rehearse]

With ``--rates`` it sweeps the offered load instead (no control): per
rate, the drain past the window tells whether the backlog grew.

Each run prints its result line (with ``compared``: the control's
``max_logit_gap`` and the program's ``program_max_logit_gap``); the last
line sums them up: the largest program gap and the smallest control gap.
The benchmark's own runs never run the control.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench.harness import run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rates", help="sweep these rates (requests/s)")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    prog, ctrl = [], []
    rates = [float(r) for r in args.rates.split(",")] if args.rates else [None]
    for rate in rates:
        for seed in [int(s) for s in args.seeds.split(",")]:
            sweep(args, seed, rate, prog, ctrl)
    if args.rates:
        return 0
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "program_gaps": prog, "control_gaps": ctrl,
                      "lower": max(prog, default=None),
                      "upper": min(ctrl, default=None)}), flush=True)
    return 0


def sweep(args, seed, rate, prog, ctrl):
    import numpy as np

    extra = ["--rate", str(rate)] if rate is not None else ["--control"]
    _, result, run = run_cell(
        ["--workload", args.workload, "--seed", str(seed),
         "--seconds", str(args.seconds), "--trace", "0"]
        + extra + (["--rehearse"] if args.rehearse else []))
    print(json.dumps(result), flush=True)
    if result is None:
        return
    if rate is not None:
        ttft = [r.stamps[0] - r.due for r in run.reqs if r.stamps]
        print(json.dumps({"rate": rate, "seed": seed,
                          "requests": len(run.reqs),
                          "drain_s": run.drain_s,
                          "ttft_p50_ms": 1e3 * float(np.median(ttft)),
                          "ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95)),
                          "metrics": result["metrics"]}),
              file=sys.stderr, flush=True)
        return
    got = result["compared"]
    prog.append(got["program_max_logit_gap"]["value"])
    ctrl.append(got["max_logit_gap"]["value"])
    print(f"seed {seed}: program {prog[-1]} control {ctrl[-1]}",
          file=sys.stderr, flush=True)


if __name__ == "__main__":
    raise SystemExit(main())
