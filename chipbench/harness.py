"""One run of one cell: build the serving path, warm it, measure, check.

Everything that belongs to one configuration, traffic mix or metric is
found by name: ``BENCHMARK.json`` names the cell's configuration file
and traffic mix (``chipbench/traffic/<traffic>.json``), the
configuration file names its architecture (``"arch"``, default
``dense``: ``chipbench/archs/<arch>.py``, which gives its sizes, program
config, weights, reference and counts), and each metric is read by
``chipbench/metrics/<metric>.py``.

The path under test is the program's normal one: ``Shell`` with the MMU
service -> ``ServingGateway(admission="fifo")`` -> ``ServingEngine`` on
slot 0, decoding through the compiled Pallas paged-attention kernel.
The window drives ``ServingGateway.submit`` and ``ServingGateway.step``
open loop, and times each request from when it was due; a mix with
``lead_s`` offers its traffic that long before the window opens.
"""
from __future__ import annotations

import argparse
import functools
import gc
import importlib.util
import json
import os
import sys
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from chipbench import traffic as T

ROOT = Path(__file__).resolve().parents[1]
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
DRAIN_LIMIT_S = 120.0          # how long past the window a request may take
SAMPLE_TOKENS = 512            # served tokens the reference checks per run
TRACE_SECONDS = 3.0            # the profiler covers the window's last 3 s


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------------ spec --
@dataclass
class Cell:
    name: str
    chips: int
    conf: dict
    arch: object               # the configuration's chipbench/archs module
    mix: dict
    metrics: List[dict]        # entries of end_to_end or per_layer


def load_cell(name: str, trace: bool, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = json.loads((root / confs[w["config"]]["file"]).read_text())
    mix = T.load_mix(root / "chipbench" / "traffic" / f"{w['traffic']}.json")
    group = bench["per_layer" if trace else "end_to_end"]
    e2e = {m["name"]: m for m in bench["end_to_end"]}

    def reported(m):
        if "workloads" in m:
            return name in m["workloads"]
        if trace:       # a per-layer metric follows its end-to-end metric
            return reported(e2e[m["moves"]])
        return True
    return Cell(name=name, chips=int(w["chips"]), conf=conf,
                arch=load_arch(conf.get("arch", "dense"), root), mix=mix,
                metrics=[m for m in group if reported(m)])


def _load(kind: str, name: str, root: Path):
    path = root / "chipbench" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str, root: Path = ROOT):
    return _load("metrics", metric, root).read


def load_arch(name: str, root: Path = ROOT):
    """``chipbench/archs/<name>.py`` of the checkout at ``root``, loaded
    once per process and checkout, so its jitted programs compile once."""
    return _load_once("archs", name, Path(root))


_load_once = functools.cache(_load)


# --------------------------------------------------------------- records --
@dataclass
class Req:
    arrival: T.Arrival
    due: float                          # absolute perf_counter time
    stream: object = None               # the gateway's TokenStream
    failed: bool = False
    stamps: List[float] = field(default_factory=list)   # per token
    seen: int = 0


@dataclass
class Run:
    """What a run recorded; the metric readers take their numbers here."""
    cell: str
    arrivals: str                       # "poisson" | "backlog"
    arch: object                        # the configuration's archs module
    dims: object                        # arch.dims_of of the configuration
    device_kind: str
    setup_s: float = 0.0
    t_open: float = 0.0
    t_close: float = 0.0
    reqs: List[Req] = field(default_factory=list)
    engine_submit: Dict[int, float] = field(default_factory=dict)
    decode_step_s: List[float] = field(default_factory=list)
    prefill_calls: List[tuple] = field(default_factory=list)
    prefill_computed: int = 0
    prefill_skipped: int = 0
    memory_peak_bytes: int = 0
    trace: object = None                # chipbench.trace.Reduced
    late_s: List[float] = field(default_factory=list)
    drain_s: float = 0.0
    t_trace0: float = 0.0               # the traced part of the window
    t_trace1: float = 0.0

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    def in_window(self, t: float) -> bool:
        return self.t_open <= t <= self.t_close

    def in_trace(self, t: float) -> bool:
        return self.t_trace0 <= t <= self.t_trace1

    def judged(self) -> List[Req]:
        """Requests whose latencies count: every request due in the
        window (open loop, not the lead-in), or every request served in
        it (backlog)."""
        if self.arrivals == "backlog":
            return [r for r in self.reqs
                    if any(self.in_window(t) for t in r.stamps)]
        return [r for r in self.reqs if r.arrival.due >= 0]

    def peaks(self) -> dict:
        from chipbench.costs import peaks
        return peaks(self.device_kind)


# ----------------------------------------------------------------- build --
def setup_compile_cache(jax) -> str:
    """JAX's persistent cache at a fixed path inside the checkout (or
    where ``JAX_COMPILATION_CACHE_DIR`` says), for every program."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def build(cfg, weights, geo: dict, seed: int, use_pallas):
    from repro.core import Shell, ShellConfig
    from repro.core.services.mmu import MMUConfig
    from repro.serve.engine import ServingEngine
    from repro.serve.gateway import ServingGateway
    shell = Shell(ShellConfig.make(
        services={"mmu": MMUConfig(page_size=geo["page_size"],
                                   n_pages=geo["n_pages"])},
        n_vfpgas=1))
    shell.build()
    eng = ServingEngine(cfg, weights, shell.services.get("mmu"),
                        max_batch=geo["max_batch"], max_len=geo["max_len"],
                        seed=seed, shell=shell, slot=0, tenant="bench",
                        prefill_chunk=geo["prefill_chunk"],
                        use_pallas=use_pallas)
    gw = ServingGateway(eng, admission="fifo")
    return shell, eng, gw


def _pow2_upto(cap: int) -> List[int]:
    out, b = [], 1
    while b < cap:
        out.append(b)
        b *= 2
    return out + [cap]


def precompile(eng, waves: List[tuple], chunk: int) -> None:
    """Compile the serving programs for every shape the ``waves`` will
    run (a wave's prompts of at most ``chunk`` tokens go through one-shot
    prefill, longer ones through chunks), several at a time: the same
    jitted functions, lowered on the engine's own arrays and argument
    kinds, so the persistent cache holds each program before the path
    first calls it.  Fewer compiles run at once for a large model, whose
    compiles take more host memory."""
    import concurrent.futures as cf

    import jax.numpy as jnp

    from repro.serve import paged_model as PM
    i32 = lambda *s: jnp.asarray(np.zeros(s, np.int32))      # noqa: E731
    f32 = lambda *s: jnp.asarray(np.zeros(s, np.float32))    # noqa: E731
    kw = dict(cfg=eng.cfg, page_size=eng.page)
    p, pools, maxp = eng.params, eng.pools, eng.max_pages
    jobs = [lambda: PM.decode_step_paged.lower(
        p, pools, eng.block_table.device_view(), eng.dev_lens,
        eng.dev_tokens, eng.rng, eng.dev_temps, eng.dev_topk, eng.dev_topp,
        eng.dev_rids, use_pallas=eng.use_pallas,
        pages_per_block=eng.pages_per_block, **kw)]
    for nb, t in waves:
        if t > chunk:
            jobs.append(lambda nb=nb: PM.prefill_chunk_paged.lower(
                p, pools, i32(nb, chunk), i32(nb), i32(nb), i32(nb, maxp),
                **kw))
        else:
            jobs.append(lambda nb=nb, t=t: PM.prefill_shared_paged.lower(
                p, pools, i32(nb, t), i32(nb), i32(nb), i32(nb),
                i32(nb, maxp), eng.rng, f32(nb), i32(nb), f32(nb), i32(nb),
                **kw))
    import jax
    weight_bytes = sum(x.nbytes for x in jax.tree.leaves(p))
    workers = 8 if weight_bytes < 1e9 else 3
    with cf.ThreadPoolExecutor(workers) as ex:
        list(ex.map(lambda job: job().compile(), jobs))


def warm_up(gw, eng, geo: dict, vocab: int, seed: int) -> None:
    """Run every shape the cell's traffic can reach, through the real
    path: each prefill batch bucket at each suffix-length bucket, each
    chunk batch, the decode step, and the eager slot-state updates for
    every row count."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed ^ 0x5EED)
    chunk = geo["prefill_chunk"]
    waves = [(nb, t) for nb in _pow2_upto(geo["max_batch"])
             for t in _pow2_upto(chunk)]
    waves += [(nb, chunk + 1) for nb in _pow2_upto(geo["max_batch"])]
    t0 = time.perf_counter()
    precompile(eng, waves, chunk)
    tp = time.perf_counter()
    log(f"precompile of {len(waves) + 1} serving programs: {tp - t0:.3f} s")
    t0 = tp
    for nb, plen in waves:
        for _ in range(nb):
            gw.submit(rng.integers(0, vocab, size=plen).tolist(),
                      max_new_tokens=2)
        gw.drain()
    t1 = time.perf_counter()
    b, p = eng.max_batch, eng.max_pages
    table = eng.block_table.device_view()
    for n in range(1, b + 1):
        idx = jnp.asarray(list(range(n)), jnp.int32)
        eng.dev_lens.at[idx].set(jnp.asarray([0] * n, jnp.int32))
        eng.dev_temps.at[idx].set(jnp.asarray([0.0] * n, jnp.float32))
        table.at[idx].set(jnp.asarray(np.full((n, p), -1, np.int32)))
    eng.flush_io(timeout=60.0)
    log(f"warm-up: {len(waves)} waves {t1 - t0:.3f} s, slot-state updates "
        f"for 1..{b} rows {time.perf_counter() - t1:.3f} s")


# ---------------------------------------------------------------- window --
class Tracer:
    """The profiler over the last ``seconds`` of the window, wrapped in
    the ``chipbench.window`` span; inner spans only while it runs."""

    def __init__(self, trace_dir: Optional[Path], seconds: float):
        self.dir, self.seconds = trace_dir, seconds
        self.on = False
        self._span = None

    def span(self, name: str):
        if not self.on:
            return nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def start(self, run: "Run") -> None:
        import jax

        from chipbench import trace as TR
        TR.start(self.dir)
        self._span = jax.profiler.TraceAnnotation(TR.WINDOW_SPAN)
        self._span.__enter__()
        self.on = True
        run.t_trace0 = time.perf_counter()

    def stop(self, run: "Run") -> None:
        import jax
        run.t_trace1 = time.perf_counter()
        self._span.__exit__(None, None, None)
        self.on = False
        jax.profiler.stop_trace()


def drive(gw, eng, run: Run, arrivals: List[T.Arrival], seconds: float,
          tracer: Optional[Tracer] = None) -> None:
    """The measured window, then (open loop) the drain of every request
    due in it.  ``tracer`` profiles the window's last seconds."""
    from repro.core.port import PortError
    span = tracer.span if tracer else (lambda name: nullcontext())
    steps_before = eng.steps
    active: List[Req] = []

    def submit(a: T.Arrival, due: float) -> None:
        r = Req(arrival=a, due=due)
        try:
            r.stream = gw.submit(a.prompt, max_new_tokens=a.max_new)
            active.append(r)
        except PortError:
            r.failed = True
        run.reqs.append(r)

    def pump() -> None:
        s0 = eng.steps
        t0 = time.perf_counter()
        with span("chipbench.step"):
            gw.step()
        t1 = time.perf_counter()
        if eng.steps > s0 and \
                run.t_open <= t0 and t1 <= run.t_open + seconds:
            run.decode_step_s.append(t1 - t0)
        with span("chipbench.record"):
            for r in active:
                n = len(r.stream.tokens)
                if n > r.seen:
                    r.stamps.extend([t1] * (n - r.seen))
                    r.seen = n
            active[:] = [r for r in active
                         if not (r.stream.done or r.stream.rejected)]

    backlog = run.arrivals == "backlog"
    if backlog:                                # queued before the window
        for a in arrivals:
            submit(a, 0.0)
    # the lead-in: arrivals due before 0 are offered, untimed, before it
    lead = 0.0 if backlog or not arrivals else max(0.0, -arrivals[0].due)
    run.t_open = time.perf_counter() + lead
    for r in run.reqs:
        r.due = run.t_open
    i = 0 if not backlog else len(arrivals)
    c0 = p0 = None
    while True:
        now = time.perf_counter()
        if now - run.t_open >= seconds:
            break
        if c0 is None and now >= run.t_open:
            c0, p0 = eng.prefill_computed, eng.prefill_skipped
        if tracer and not tracer.on and run.t_trace1 == 0.0 and \
                now - run.t_open >= seconds - tracer.seconds:
            tracer.start(run)
        with span("chipbench.submit"):
            while i < len(arrivals) and \
                    arrivals[i].due <= now - run.t_open:
                due = run.t_open + arrivals[i].due
                run.late_s.append(time.perf_counter() - due)
                submit(arrivals[i], due)
                i += 1
        if gw.pending():
            pump()
        else:
            nxt = (run.t_open + arrivals[i].due if i < len(arrivals)
                   else run.t_open + seconds)
            with span("chipbench.idle"):
                time.sleep(max(0.0, min(nxt, run.t_open + seconds)
                               - time.perf_counter()))
    run.t_close = time.perf_counter()
    if tracer and tracer.on:
        tracer.stop(run)
    if c0 is None:
        c0, p0 = eng.prefill_computed, eng.prefill_skipped
    if not backlog:
        limit = run.t_close + DRAIN_LIMIT_S
        while active and time.perf_counter() < limit:
            pump()
        for r in active:
            r.failed = True
        run.drain_s = time.perf_counter() - run.t_close
        log(f"drain {run.drain_s:.3f} s past the "
            f"window; {len(active)} requests unfinished")
    run.prefill_computed = eng.prefill_computed - c0
    run.prefill_skipped = eng.prefill_skipped - p0
    live = [r for r in eng.slots if r is not None] + list(eng.queue)
    for req in list(eng.completed) + live:
        run.engine_submit[req.rid] = req.t_submit
    log(f"window {run.window_s:.3f} s after {lead:.3f} s of lead-in: "
        f"{len(run.reqs)} requests, "
        f"{eng.steps - steps_before} engine steps (drain included); "
        f"generator late by median "
        f"{1e3 * float(np.median(run.late_s or [0])):.3f} ms, "
        f"max {1e3 * max(run.late_s or [0]):.3f} ms")


# ----------------------------------------------------------- correctness --
def sample_for_check(run: Run, seed: int) -> List[Req]:
    """The longest finished request and a seeded draw of others, up to
    ``SAMPLE_TOKENS`` served tokens."""
    done = [r for r in run.reqs
            if r.stream is not None and r.stream.done
            and not r.stream.rejected and r.stream.tokens]
    if not done:
        return []
    done.sort(key=lambda r: -(len(r.arrival.prompt) + len(r.stream.tokens)))
    out = [done[0]]
    total = len(done[0].stream.tokens)
    rng = np.random.default_rng(seed ^ 0xC4EC)
    for j in rng.permutation(np.arange(1, len(done))):
        if total >= SAMPLE_TOKENS:
            break
        out.append(done[j])
        total += len(done[j].stream.tokens)
    return out


def check(arch, weights, dims, sample: List[Req], pad_to: int,
          control: bool = False):
    prog, ctrl, n = 0.0, 0.0, 0
    for r in sample:
        g, c = arch.gaps(weights, dims, r.arrival.prompt, r.stream.tokens,
                         pad_to=pad_to, control=control)
        prog = max(prog, float(g.max()))
        n += len(g)
        if control:
            ctrl = max(ctrl, float(c.max()))
    return prog, (ctrl if control else None), n


# ------------------------------------------------------------------ main --
def parse_args(argv):
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: reduced sizes, interpret kernels")
    ap.add_argument("--control", action="store_true",
                    help="judge the float8 control's tokens in the program's "
                         "place (calibration; must read not correct)")
    ap.add_argument("--rate", type=float,
                    help="override the mix's rate (knee sweeps only)")
    ap.add_argument("--fault", choices=("token", "state"),
                    help="break the timed path (tests of the check)")
    return ap.parse_args(argv)


def main(argv=None, *, t0: Optional[float] = None, root: Path = ROOT) -> int:
    """One run; ``root`` is the checkout whose ``BENCHMARK.json``,
    configuration, traffic and metric files name the cell.  Prints the
    result line and returns the exit code."""
    rc, result, _ = run_cell(argv, t0=t0, root=root)
    if result is not None:
        print(json.dumps(result), flush=True)
    return rc


def run_cell(argv=None, *, t0: Optional[float] = None, root: Path = ROOT):
    """One run: ``(exit code, result or None, the Run's records)``."""
    t0 = time.perf_counter() if t0 is None else t0
    args = parse_args(argv)
    cell = load_cell(args.workload, bool(args.trace), root)
    if args.rate is not None:
        cell.mix["rate_per_s"] = args.rate
        log(f"rate overridden: {args.rate} requests/s")
    import jax

    devs = jax.devices()
    dev = devs[0]
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    if dev.platform != "tpu" and not args.rehearse:
        log(f"JAX found no TPU (platform={dev.platform}): no result")
        return 3, None, None
    if len(devs) < cell.chips:
        log(f"cell needs {cell.chips} chips, JAX sees {len(devs)}: no result")
        return 3, None, None
    if not args.rehearse:
        log(f"compile cache {setup_compile_cache(jax)}")
    compiles = [0]
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **kw: compiles.__setitem__(
            0, compiles[0] + (ev == BACKEND_COMPILE)))

    from repro.serve.paged_model import TRACE_COUNTS
    conf, arch = cell.conf, cell.arch
    dims = arch.dims_of(conf, rehearse=args.rehearse)
    geo = conf["rehearse_engine" if args.rehearse else "engine"]
    cfg = arch.program_config(conf["name"], dims)
    eng_seed = int(args.seed) % (1 << 31)
    t_w = time.perf_counter()
    weights = jax.block_until_ready(arch.make_weights(dims, args.seed))
    log(f"weights on the device: {time.perf_counter() - t_w:.3f} s "
        f"(process start to here {t_w - t0:.3f} s)")
    arrivals = T.generate(cell.mix, vocab=dims.vocab, seed=args.seed,
                          seconds=args.seconds,
                          scale=geo["max_len"] / conf["engine"]["max_len"],
                          max_len=geo["max_len"])
    shell, eng, gw = build(cfg, weights, geo, eng_seed,
                           use_pallas=True if args.rehearse else None)
    if args.fault:
        break_path(eng, args.fault, dims.vocab)
    warm_up(gw, eng, geo, dims.vocab, eng_seed)
    run = Run(cell=cell.name, arrivals=cell.mix["arrivals"], arch=arch,
              dims=dims, device_kind=dev.device_kind)
    if args.trace:
        record_prefill_calls(eng, run)
    trace_dir = root / ".chipbench_trace" / cell.name
    c_before = compiles[0]
    tr_before = sum(TRACE_COUNTS.values())
    tracer = None
    if args.trace:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracer = Tracer(trace_dir, min(args.seconds, TRACE_SECONDS))
    run.setup_s = time.perf_counter() - t0
    drive(gw, eng, run, arrivals, args.seconds, tracer)
    # nothing may compile inside the window: the log says if it did
    log(f"set-up {run.setup_s:.3f} s with {c_before} backend compiles; "
        f"inside window and drain: {compiles[0] - c_before} backend "
        f"compiles, {sum(TRACE_COUNTS.values()) - tr_before} traces of the "
        f"serving programs")
    stats = dev.memory_stats() or {}
    run.memory_peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    eng.flush_io(timeout=60.0)
    shell.close()
    eng.pools = None
    del eng, gw, shell
    gc.collect()
    if args.trace and dev.platform == "tpu":
        from chipbench import trace as TR
        run.trace = TR.reduce(TR.read(TR.find_xplane(trace_dir)))

    sample = sample_for_check(run, args.seed)
    t_check = time.perf_counter()
    gap, ctrl, n_checked = check(arch, weights, dims, sample,
                                 pad_to=geo["max_len"],
                                 control=args.control)
    log(f"reference check of {len(sample)} requests, {n_checked} served "
        f"tokens: {time.perf_counter() - t_check:.3f} s")
    key = "rehearse_max_logit_gap" if args.rehearse else "max_logit_gap"
    limit = conf["correct"][key]
    # the control: the float8 forward's first tokens where the program's were
    judged_gap = ctrl if args.control else gap
    correct = bool(sample) and limit is not None and judged_gap <= limit
    compared = {"max_logit_gap": {"value": judged_gap, "limit": limit},
                "served_tokens_checked": {"value": n_checked,
                                          "limit": "at least 1"}}
    if args.control:
        compared["program_max_logit_gap"] = {"value": gap, "limit": limit}

    metrics = {}
    for m in cell.metrics:
        v = load_reader(m["name"], root)(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    attempted = len(run.judged())
    failed = sum(r.failed for r in run.reqs)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if run.trace is not None:
        from chipbench import trace as TR
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {"device_ops": TR.top_ops(run.trace),
                               "idle_gaps": [list(g)
                                             for g in run.trace.gaps]}
    result["compared"] = compared
    for k, v in compared.items():
        print(f"compared {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    return 0, result, run


# -------------------------------------------------- tracing and faults --
def record_prefill_calls(eng, run: Run) -> None:
    """Traced runs only: note each prefill call's query starts and
    lengths (for the FLOP count), around the engine's own callables."""
    def wrap(kind, fn, qs_at, ql_at):
        def call(*a, **kw):
            run.prefill_calls.append((kind, time.perf_counter(),
                                      np.asarray(a[qs_at]),
                                      np.asarray(a[ql_at])))
            return fn(*a, **kw)
        return call
    # (params, pools, tokens, q_lens, q_starts, ...) for both programs
    eng._prefill_shared = wrap("shared", eng._prefill_shared, 4, 3)
    eng._prefill_chunk = wrap("chunk", eng._prefill_chunk, 4, 3)


def break_path(eng, fault: str, vocab: int) -> None:
    """Faults the check must catch: ``token`` alters every decoded token
    where it is produced; ``state`` has the decode step return its KV
    pools unchanged."""
    import jax
    import jax.numpy as jnp
    step = eng._decode_step

    def altered(*a, **kw):
        toks, *rest = step(*a, **kw)
        return ((toks + 1) % vocab, *rest)

    def unchanged(params, pools, *a, **kw):
        toks, _, lens, rng = step(params, jax.tree.map(jnp.copy, pools),
                                  *a, **kw)
        return toks, pools, lens, rng
    eng._decode_step = altered if fault == "token" else unchanged
