"""Record the small chip trace that ``test_chipbench_trace.py`` reads.

On one TPU chip: a reduced smollm engine (2 layers, d_model 128) behind
the gateway serves four short requests; the profiler traces a window of
a few steps, inside the benchmark's own host spans.  The trace is copied
to ``--out`` (kept as
``chipbench/tests/data/small_trace.xplane.pb``) and a summary of its
planes, lines and event names is printed, which is how the reduction in
``chipbench/trace.py`` learned the names.

    python3 chipbench/tests/record_trace.py [--out DIR]
"""
from __future__ import annotations

import argparse
import collections
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=str(ROOT / ".chipbench_trace" / "recorded"))
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    from chipbench.harness import load_arch
    from repro.core import Shell, ShellConfig
    from repro.core.services.mmu import MMUConfig
    from repro.serve.engine import ServingEngine
    from repro.serve.gateway import ServingGateway

    conf = json.loads(
        (ROOT / "chipbench/tests/data/smollm-135m.json").read_text())
    arch = load_arch("dense")
    dims = arch.dims_of(conf, rehearse=True)
    cfg = arch.program_config("smollm-135m", dims)
    w = arch.make_weights(dims, 1)
    shell = Shell(ShellConfig.make(
        services={"mmu": MMUConfig(page_size=16, n_pages=128)}, n_vfpgas=1))
    shell.build()
    eng = ServingEngine(cfg, w, shell.services.get("mmu"), max_batch=4,
                        max_len=256, seed=1, shell=shell, slot=0,
                        tenant="trace", prefill_chunk=32)
    gw = ServingGateway(eng, admission="fifo")
    prompts = [[(7 * i + j) % dims.vocab for j in range(n)]
               for i, n in enumerate((20, 40, 50, 70))]
    for p in prompts:                                  # warm every shape
        gw.submit(p, max_new_tokens=3)
    gw.drain()
    tmp = Path(tempfile.mkdtemp())
    from chipbench import trace
    trace.start(tmp)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for p in prompts:
            with jax.profiler.TraceAnnotation("chipbench.submit"):
                gw.submit(p, max_new_tokens=3)
        while gw.pending():
            with jax.profiler.TraceAnnotation("chipbench.step"):
                gw.step()
    jax.profiler.stop_trace()
    shell.close()
    found = sorted(tmp.rglob("*.xplane.pb"))
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    dst = out / "small_trace.xplane.pb"
    shutil.copy(found[-1], dst)
    pd = jax.profiler.ProfileData.from_file(str(dst))
    for plane in pd.planes:
        for line in plane.lines:
            names = collections.Counter(e.name for e in line.events)
            evs = list(line.events)
            t0 = min((e.start_ns for e in evs), default=0)
            print(f"plane={plane.name!r} line={line.name!r} "
                  f"events={len(evs)} first_start_ns={t0} "
                  f"top={names.most_common(6)}")
    print(f"wrote {dst} ({dst.stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
