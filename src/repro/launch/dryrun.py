import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST stay the first statements in this module — jax
locks the device count at first init, and the production meshes need 512
placeholder host devices.  Never import this module from tests/benches
(they must see 1 device); it is a CLI:

    PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m \
        --shape train_4k --mesh pod

Results (memory analysis, cost analysis, collective schedule, roofline
terms) are written incrementally to experiments/dryrun/<mesh>/<arch>__<shape>.json
so the 40-cell × 2-mesh sweep is resumable.
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs import ALL_SHAPES, ARCHS, get_config, get_shape, shape_applicable
from repro.launch.mesh import make_production_mesh, mesh_chips
from repro.launch.steps import make_bundle
from repro.telemetry import roofline as R

DEFAULT_OUT = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def run_cell(arch_id: str, shape_name: str, mesh_kind: str, *,
             out_dir: Path = DEFAULT_OUT, force: bool = False,
             bundle_kw=None, tag: str = "") -> dict:
    cfg = get_config(arch_id)
    shape = get_shape(shape_name)
    out_path = out_dir / mesh_kind / f"{arch_id}__{shape_name}{tag}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    rec = {"arch": arch_id, "shape": shape_name, "mesh": mesh_kind,
           "tag": tag, "status": "pending"}
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        rec.update(status="skipped", reason=why)
        _write(out_path, rec)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    chips = mesh_chips(mesh)
    try:
        with mesh:
            bundle = make_bundle(cfg, shape, mesh, **(bundle_kw or {}))
            t0 = time.perf_counter()
            lowered = bundle.lower()
            t1 = time.perf_counter()
            compiled = lowered.compile()
            t2 = time.perf_counter()

        mem = R.memory_stats(compiled)
        print(f"[{arch_id}/{shape_name}/{mesh_kind}] memory_analysis:", mem)
        ca = compiled.cost_analysis() or {}
        print(f"[{arch_id}/{shape_name}/{mesh_kind}] cost_analysis: "
              f"flops={ca.get('flops', 0):.3e} "
              f"bytes={ca.get('bytes accessed', 0):.3e}")

        mf = R.model_flops_for(cfg, shape)
        fused = (bundle_kw or {}).get("attention_impl") == "fused"
        extra = R.fused_boundary_bytes(cfg, shape, chips) if fused else 0.0
        roof = R.analyze(
            compiled, chips=chips, model_flops=mf,
            discount_scope="vmem_fused" if fused else None,
            extra_bytes_per_device=extra)
        rec.update(
            status="ok",
            step=bundle.name,
            bundle_kw={k: str(v) for k, v in (bundle_kw or {}).items()},
            chips=chips,
            lower_s=t1 - t0,
            compile_s=t2 - t1,
            memory_analysis=mem,
            cost_analysis={k: float(v) for k, v in ca.items()
                           if isinstance(v, (int, float))},
            roofline=roof.as_dict(),
        )
    except Exception as e:  # a failing cell is a bug in our sharding
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-4000:])
    _write(out_path, rec)
    return rec


def _write(path: Path, rec: dict):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(rec, indent=1))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", type=Path, default=DEFAULT_OUT)
    args = ap.parse_args(argv)

    archs = sorted(ARCHS) if args.arch == "all" else args.arch.split(",")
    shapes = ([s.name for s in ALL_SHAPES] if args.shape == "all"
              else args.shape.split(","))
    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]

    n_ok = n_skip = n_err = 0
    for mesh_kind in meshes:
        for arch in archs:
            for shape in shapes:
                t0 = time.perf_counter()
                rec = run_cell(arch, shape, mesh_kind, out_dir=args.out,
                               force=args.force)
                jax.clear_caches()
                dt = time.perf_counter() - t0
                st = rec["status"]
                n_ok += st == "ok"
                n_skip += st == "skipped"
                n_err += st == "error"
                extra = ""
                if st == "ok":
                    r = rec["roofline"]
                    extra = (f"dom={r['dominant']} "
                             f"c={r['compute_s']:.3e}s m={r['memory_s']:.3e}s "
                             f"x={r['collective_s']:.3e}s "
                             f"frac={r['roofline_fraction']:.3f}")
                elif st == "error":
                    extra = rec["error"][:120]
                print(f"{st.upper():7s} {mesh_kind}/{arch}/{shape} "
                      f"({dt:.1f}s) {extra}", flush=True)
    print(f"done: ok={n_ok} skipped={n_skip} error={n_err}")
    return 0 if n_err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
