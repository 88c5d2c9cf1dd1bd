#!/usr/bin/env python3
"""Smoke run of the serving main path on a TPU — a start-up check, not a
benchmark.

One chip (the default): smollm-135m at its published widths (30 layers,
d_model 576, 9/3 heads, head_dim 64, vocab 49152) with random float32
weights from ``--seed``, served through the normal entry points:
``Shell`` with the MMU service -> ``ServingGateway`` -> ``ServingEngine``
bound to slot 0, decoding through the compiled Pallas paged-attention
kernel.  It fails unless every request completes, billed I/O and the MMU
end clean, the decode step compiled once, and the kernel's decode logits
match the XLA reference path on the same pool state.

``--chips 4`` runs only the tensor-parallel phase: the same prompts
through ``ServingEngine(mesh=make_host_mesh(1, 4))``, compared with a
one-chip engine on device 0.

    python chip_smoke.py
    python chip_smoke.py --chips 4
    JAX_PLATFORMS=cpu python chip_smoke.py --cpu-rehearsal [--chips 4]

Without a TPU the script exits non-zero and prints no result, unless
``--cpu-rehearsal`` is given: that runs the same phases at the reduced
2-layer config with the kernels in interpret mode.  The last line of
stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

PROMPT_LENS = (16, 24, 40, 57, 90, 128, 150, 200)
MAX_NEW = 16
MAX_BATCH = 8
PAGE = 32
N_PAGES = 256
MAX_LEN = 256
PREFILL_CHUNK = 64            # prompts above this stream in as chunks
PARITY_STEPS = 4
# Kernel vs XLA reference, both at float32 contraction precision: they
# differ only in summation order inside attention, so logits should agree
# to float32 rounding grown over 30 layers.  1e-3 of the logit scale is
# ~100x that and far below any real indexing or masking error, which
# moves logits by O(scale).
LOGIT_RTOL = 1e-3
# Greedy streams of two correct paths may part only at a near-tie: a
# reference top-2 gap this small (relative to the logit scale).
TIE_RTOL = 1e-3
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(Exception):
    """A check of the smoke run did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileClock:
    """Sums JAX's backend-compile durations (persistent-cache reads
    included, so a warm cache shows as a small number) and cache hits."""

    def __init__(self, monitoring):
        self.secs = 0.0
        self.compiles = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == BACKEND_COMPILE:
            self.secs += secs
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == CACHE_HIT:
            self.cache_hits += 1


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run on the CPU at the reduced config")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.cpu_rehearsal and args.chips == 4:
        os.environ.setdefault("XLA_FLAGS",
                              "--xla_force_host_platform_device_count=4")
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        print(f"chip_smoke: JAX found no TPU (platform={dev.platform}); "
              "pass --cpu-rehearsal to rehearse on the CPU",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devs)}", file=sys.stderr)
        return 2
    log(f"SMOKE RUN, not a benchmark: device platform={dev.platform} "
        f"kind={dev.device_kind} count={len(devs)} "
        f"compile_cache={cache_dir}")
    clock = CompileClock(jax.monitoring)
    try:
        if args.chips == 4:
            phase_tp(args, clock)
        else:
            phase_one_chip(args, clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'not reported')}"
        f" (device 0, smoke run)")
    result = {"ok": True,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devs)}}
    if args.cpu_rehearsal:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


# ----------------------------------------------------------------- setup --
def build(args):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.models import transformer as T

    cfg = get_config("smollm-135m")
    if args.cpu_rehearsal:
        cfg = cfg.reduced()
    params = T.init_params(jax.random.PRNGKey(args.seed), cfg,
                           dtype=jnp.float32)
    rng = np.random.RandomState(args.seed)
    prompts = [rng.randint(3, cfg.vocab_size, size=n).tolist()
               for n in PROMPT_LENS]
    log(f"model smollm-135m{' (reduced)' if args.cpu_rehearsal else ''}: "
        f"layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} "
        f"head_dim={cfg.resolved_head_dim} vocab={cfg.vocab_size} "
        f"dtype=float32; {len(prompts)} prompts of {PROMPT_LENS} tokens, "
        f"{MAX_NEW} new tokens each")
    return cfg, params, prompts


def greedy_streams(engine, prompts):
    """Serve ``prompts`` greedily through a bare engine; rid order."""
    rids = [engine.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
    engine.run()
    by_rid = {r.rid: r.out_tokens for r in engine.completed}
    return [by_rid[r] for r in rids]


# --------------------------------------------------------- one-chip run --
def phase_one_chip(args, clock):
    from repro.kernels import on_tpu

    cfg, params, prompts = build(args)
    phase_serve(cfg, params, prompts, args.seed, clock)
    phase_parity(cfg, params, prompts, require_kernel=on_tpu())


def phase_serve(cfg, params, prompts, seed, clock):
    """Shell + MMU service -> gateway -> slot-0 engine, two rounds."""
    from repro.core import Shell, ShellConfig
    from repro.core.services.mmu import MMUConfig
    from repro.kernels import on_tpu
    from repro.serve.engine import ServingEngine
    from repro.serve.gateway import ServingGateway
    from repro.serve.paged_model import TRACE_COUNTS

    shell = Shell(ShellConfig.make(
        services={"mmu": MMUConfig(page_size=PAGE, n_pages=N_PAGES)},
        n_vfpgas=1))
    shell.build()
    try:
        mmu = shell.services.get("mmu")
        eng = ServingEngine(cfg, params, mmu, max_batch=MAX_BATCH,
                            max_len=MAX_LEN, seed=seed, shell=shell, slot=0,
                            tenant="smoke", prefill_chunk=PREFILL_CHUNK)
        check(eng.use_pallas == on_tpu(),
              f"engine use_pallas={eng.use_pallas} on "
              f"{'a TPU' if on_tpu() else 'the CPU'}")
        log(f"engine decode kernel: "
            f"{'compiled Pallas' if eng.use_pallas else 'XLA reference'}")
        gw = ServingGateway(eng, admission="fifo")
        traces0 = TRACE_COUNTS.get("decode_step_paged", 0)
        rounds = {}
        # round 1 compiles every shape; round 2 replays the same traffic
        # warm, so its steps time the steady state
        for name in ("cold", "warm"):
            c0, steps0 = clock.secs, eng.steps
            t0 = time.perf_counter()
            streams = [gw.submit(p, max_new_tokens=MAX_NEW)
                       for p in prompts]
            gw.drain()
            wall = time.perf_counter() - t0
            bad = [s.gid for s in streams if not s.done or s.rejected]
            check(not bad, f"{name} round: requests {bad} did not complete")
            for s in streams:
                check(len(s.tokens) == MAX_NEW
                      and all(0 <= t < cfg.vocab_size for t in s.tokens),
                      f"{name} round: request {s.gid} emitted "
                      f"{len(s.tokens)} tokens {s.tokens[:4]}...")
            rounds[name] = [s.tokens for s in streams]
            steps = eng.steps - steps0
            log(f"{name} round: {len(streams)} requests, {steps} decode "
                f"steps, wall {wall:.3f} s, backend compile "
                f"{clock.secs - c0:.3f} s (smoke run)")
        st = gw.stats()
        check(st["expired"] == 0 and st["rejected_infeasible"] == 0
              and st["rejected_full"] == 0 and not gw.rejected,
              f"gateway rejected or expired requests: {st}")
        check(eng.flush_io(timeout=60.0), "billed decode I/O did not drain")
        check(eng.io_failures == 0, f"io_failures={eng.io_failures}")
        used = mmu.utilization()["pages_used"]
        check(used == 0, f"MMU reports {used} pages in use after drain")
        traces = TRACE_COUNTS["decode_step_paged"] - traces0
        check(traces == 1, f"decode step traced {traces} times, want 1")
        check(rounds["cold"] == rounds["warm"],
              "greedy streams differ between identical rounds")
        log(f"served through shell/gateway/engine: io_bytes={eng.io_bytes} "
            f"io_failures=0 pages_in_use=0 decode_traces=1; decode step "
            f"{eng.ewma_decode_step_s * 1e3:.3f} ms (engine EWMA, host "
            f"clock, batch {MAX_BATCH}, smoke run)")
        log(f"compile total {clock.secs:.3f} s over {clock.compiles} "
            f"backend compiles, {clock.cache_hits} persistent-cache hits "
            f"(smoke run)")
    finally:
        shell.close()


def phase_parity(cfg, params, prompts, *, require_kernel):
    """Decode logits of the Pallas kernel vs the XLA reference on one pool
    state: the prompts prefilled into scattered pages, then
    ``PARITY_STEPS`` greedy steps advanced along the kernel path."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serve.paged_model import (decode_logits_paged, make_pools,
                                         prefill_shared_paged)

    n = len(prompts)
    maxp = MAX_LEN // PAGE
    lens = np.array([len(p) for p in prompts], np.int32)
    # a seeded permutation scatters each row over the pool, so the kernel
    # walks real page tables; pages past a row's need stay unmapped (-1)
    tables = np.random.RandomState(1).permutation(N_PAGES)[:n * maxp]
    tables = tables.reshape(n, maxp).astype(np.int32)
    for i, ln in enumerate(lens):
        tables[i, -(-(int(ln) + PARITY_STEPS) // PAGE):] = -1
    tokens = np.zeros((n, MAX_LEN), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
    zeros = jnp.zeros((n,), jnp.int32)
    tables_d = jnp.asarray(tables)
    hlo = decode_logits_paged.lower(
        params, jax.eval_shape(lambda: make_pools(cfg, N_PAGES, PAGE)),
        tables_d, jnp.asarray(lens), zeros, cfg=cfg, page_size=PAGE,
        use_pallas=True).as_text()
    check(not require_kernel or "tpu_custom_call" in hlo,
          "the Pallas decode program holds no compiled TPU kernel")
    worst, flips = 0.0, []
    with jax.default_matmul_precision("highest"):
        first, pools, _ = prefill_shared_paged(
            params, make_pools(cfg, N_PAGES, PAGE), jnp.asarray(tokens),
            jnp.asarray(lens), zeros, zeros, tables_d,
            jax.random.PRNGKey(0), jnp.zeros((n,), jnp.float32),
            cfg=cfg, page_size=PAGE)
        last, cur = first, jnp.asarray(lens)
        for step in range(PARITY_STEPS):
            lp, new_pools = decode_logits_paged(
                params, pools, tables_d, cur, last, cfg=cfg,
                page_size=PAGE, use_pallas=True)
            lr, _ = decode_logits_paged(
                params, pools, tables_d, cur, last, cfg=cfg,
                page_size=PAGE, use_pallas=False)
            lp, lr = np.asarray(lp), np.asarray(lr)
            check(np.isfinite(lp).all() and lp.shape == (n, cfg.vocab_size),
                  f"kernel logits not finite or shaped {lp.shape}")
            scale = float(np.abs(lr).max())
            err = float(np.abs(lp - lr).max()) / scale
            worst = max(worst, err)
            for i in np.flatnonzero(lp.argmax(-1) != lr.argmax(-1)):
                top2 = np.sort(lr[i])[-2:]
                flips.append((step, int(i),
                              float(top2[1] - top2[0]) / scale))
            pools, last, cur = new_pools, jnp.asarray(lp.argmax(-1)), cur + 1
    # one decode program per path at the serving precision, warm: the
    # model half of a step without host work (smoke timing, host clock)
    for use_pallas in (True, False):
        def run():
            return decode_logits_paged(
                params, pools, tables_d, cur, last, cfg=cfg,
                page_size=PAGE, use_pallas=use_pallas)[0].block_until_ready()
        run()
        t0 = time.perf_counter()
        for _ in range(5):
            run()
        log(f"decode logits program, "
            f"{'Pallas kernel' if use_pallas else 'XLA reference'}: "
            f"{(time.perf_counter() - t0) / 5 * 1e3:.3f} ms per call "
            f"(batch {n}, warm, host clock, smoke run)")
    log(f"parity: max |logit_kernel - logit_ref| / max|logit_ref| = "
        f"{worst:.3e} over {PARITY_STEPS} decode steps x {n} rows "
        f"(tolerance {LOGIT_RTOL:g}, float32 contractions)")
    check(worst <= LOGIT_RTOL,
          f"kernel logits differ from the reference by {worst:.3e}")
    for step, row, gap in flips:
        log(f"parity: greedy token differs at step {step} row {row}; "
            f"reference top-2 gap {gap:.3e} of scale (near-tie)")
        check(gap <= TIE_RTOL,
              f"greedy token differs at step {step} row {row} with "
              f"top-2 gap {gap:.3e}: not a near-tie")


# ------------------------------------------------------ four-chip phase --
def phase_tp(args, clock):
    """Tensor-parallel serving over ``make_host_mesh(1, 4)`` vs one chip."""
    import jax

    from repro.core.services.mmu import MMU, MMUConfig
    from repro.launch.mesh import make_host_mesh
    from repro.serve.engine import ServingEngine

    cfg, params, prompts = build(args)

    def engine(mesh):
        mmu = MMU(MMUConfig(page_size=PAGE, n_pages=N_PAGES))
        return ServingEngine(cfg, params, mmu, max_batch=MAX_BATCH,
                             max_len=MAX_LEN, seed=args.seed,
                             prefill_chunk=PREFILL_CHUNK, mesh=mesh)

    mesh = make_host_mesh(1, 4)
    tp = engine(mesh)
    plan = tp.tp
    check(plan is not None, "a 4-way model axis did not build a TP context")
    devices = set()
    for leaf in jax.tree.leaves(tp.params) + list(tp.pools.values()):
        devices |= set(leaf.sharding.device_set)
    check(len(devices) == 4,
          f"params and pools span {len(devices)} devices, want 4")
    ffn = tp.params["layers"]["ffn"]["w_gate"]
    local = ffn.addressable_shards[0].data.shape[-1]
    check(not plan.shard_mlp or local * 4 == cfg.d_ff,
          f"w_gate shard holds {local} of d_ff={cfg.d_ff}")
    log(f"TP=4 plan: shard_heads={plan.shard_heads} "
        f"shard_mlp={plan.shard_mlp}; params and pools span 4 devices; "
        f"w_gate shard {local}/{cfg.d_ff}. "
        + (f"Heads are not sharded ({cfg.n_heads} q / {cfg.n_kv_heads} kv "
           "heads do not both divide 4), so this phase checks placement "
           "and the per-layer MLP psum, not head sharding."
           if not plan.shard_heads else ""))
    c0 = clock.secs
    t0 = time.perf_counter()
    got = greedy_streams(tp, prompts)
    log(f"TP=4 engine: {len(got)} requests in {time.perf_counter() - t0:.3f}"
        f" s wall, backend compile {clock.secs - c0:.3f} s (smoke run)")
    c0 = clock.secs
    t0 = time.perf_counter()
    want = greedy_streams(engine(None), prompts)
    log(f"one-chip engine (device 0): {len(want)} requests in "
        f"{time.perf_counter() - t0:.3f} s wall, backend compile "
        f"{clock.secs - c0:.3f} s (smoke run)")
    check(all(len(t) == MAX_NEW for t in got),
          "TP engine emitted short streams")
    not_ties = []
    for i, (g, w) in enumerate(zip(got, want)):
        if g == w:
            continue
        j = next(k for k in range(MAX_NEW) if g[k] != w[k])
        gap, noise = tie_margin(cfg, params, prompts[i] + w[:j], w[j], g[j])
        log(f"TP=4 stream {i} parts from one chip at token {j}: reference "
            f"gap between the two picks {gap:.3e} of scale, serving-"
            f"precision noise {noise:.3e} of scale")
        # each engine's logits sit within ``noise`` of the reference, so
        # two engines can only order the picks differently within 2x that
        if gap > 2 * noise:
            not_ties.append((i, j))
    check(not not_ties,
          f"TP streams part from one chip away from a near-tie "
          f"(stream, token): {not_ties}")
    same = sum(g == w for g, w in zip(got, want))
    log(f"TP=4 vs one chip (device 0): {same}/{len(got)} greedy streams "
        f"identical" + (", the rest parted at near-ties"
                        if same < len(got) else ""))
    check(tp.mmu.utilization()["pages_used"] == 0,
          "TP engine left pages in use")


def tie_margin(cfg, params, tokens, a, b):
    """After ``tokens``, the dense float32 model's logit gap between
    tokens ``a`` and ``b`` at highest precision, and how far its logits
    move at the default (serving) matmul precision: both relative to
    the logit scale.  The dense model stands in for the engines' own
    rounding, which shares its weights, dtypes and matmul precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.transformer import forward, lm_logits

    pad = np.zeros((1, MAX_LEN), np.int32)
    pad[0, :len(tokens)] = tokens

    def logits():
        hidden = forward(params, cfg, jnp.asarray(pad))[0]
        return np.asarray(lm_logits(params, cfg, hidden[0, len(tokens) - 1])
                          [:cfg.vocab_size])

    with jax.default_matmul_precision("highest"):
        ref = logits()
    scale = float(np.abs(ref).max())
    return (abs(float(ref[a] - ref[b])) / scale,
            float(np.abs(logits() - ref).max()) / scale)


if __name__ == "__main__":
    raise SystemExit(main())
