"""Serving launcher: paged continuous-batching engine over the MMU service.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
        --requests 16 --max-new 16 --batch 8

Serves the published widths by default; ``--reduced`` serves the 2-layer
cut used by the CPU tests.  The decode kernel follows the platform: the
compiled Pallas kernel on a TPU, the XLA reference elsewhere.
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.services.mmu import MMU, MMUConfig
from repro.launch.compile_cache import use_compile_cache
from repro.models import transformer as T
from repro.serve.engine import ServingEngine


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=32)
    ap.add_argument("--n-pages", type=int, default=512)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--reduced", action="store_true",
                    help="serve the 2-layer reduced config")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache_dir = use_compile_cache()
    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} compile_cache={cache_dir}")
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = T.init_params(jax.random.PRNGKey(args.seed), cfg,
                           dtype=jnp.float32)
    mmu = MMU(MMUConfig(page_size=args.page_size, n_pages=args.n_pages))
    eng = ServingEngine(cfg, params, mmu, max_batch=args.batch,
                        max_len=args.max_len, seed=args.seed)

    rng = np.random.RandomState(args.seed)
    for _ in range(args.requests):
        plen = int(rng.randint(4, 48))
        eng.submit(rng.randint(3, cfg.vocab_size, size=plen).tolist(),
                   max_new_tokens=args.max_new,
                   temperature=args.temperature)
    stats = eng.run()
    lat = [r.t_first_token - r.t_submit for r in eng.completed]
    stats["ttft_p50_s"] = float(np.percentile(lat, 50)) if lat else 0.0
    stats["mmu"] = eng.mmu.utilization()
    stats["use_pallas"] = eng.use_pallas
    print(json.dumps(stats, indent=1, default=str))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
