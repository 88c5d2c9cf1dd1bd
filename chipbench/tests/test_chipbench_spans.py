"""The serving program's host spans and their readers: a few gateway steps
of a tiny shell-bound engine traced on the CPU, the per-step split on
hand-made spans, and the reduction of the recorded chip trace, which
holds no program spans, unchanged."""
import os
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness, spans, trace  # noqa: E402
from chipbench.spans import Span  # noqa: E402

SMALL = Path(__file__).parent / "data" / "small_trace.xplane.pb"
CELL = "spans-test"
METRICS = ("device_wait_ms", "shell_host_ms", "engine_host_ms",
           "shell_thread_ms")

# the serving thread's spans and the arguments each carries
SERVING = {
    "repro.engine.step": {"live", "emitted"},
    "repro.health.beat": set(),
    "repro.gateway.backfill": {"dispatched"},
    "repro.engine.admit": {"admitted"},
    "repro.mmu.admit": {"pages"},
    "repro.engine.prefill": {"kind", "rows", "tokens"},
    "repro.engine.prefill_wait": set(),
    "repro.mmu.block_table": {"rows"},
    "repro.engine.slot_sync": {"rows"},
    "repro.engine.decode_dispatch": set(),
    "repro.engine.decode_wait": set(),
    "repro.port.submit": {"tag", "nbytes"},
    "repro.mmu.extend": {"rows", "freed"},
}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Four requests (one chunk-prefilled) through the gateway of a
    shell-bound engine, traced after a warm-up at the same shapes; the
    trace is where the harness keeps a cell's trace."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core import Shell, ShellConfig
    from repro.core.services.mmu import MMUConfig
    from repro.models import transformer as T
    from repro.serve.engine import ServingEngine
    from repro.serve.gateway import ServingGateway
    from repro.serve.paged_model import TRACE_COUNTS

    cfg = get_config("smollm-135m").reduced()
    params = T.init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    shell = Shell(ShellConfig.make(
        services={"mmu": MMUConfig(page_size=16, n_pages=64)}, n_vfpgas=1))
    shell.build()
    eng = ServingEngine(cfg, params, shell.services.get("mmu"), max_batch=4,
                        max_len=128, seed=1, shell=shell, slot=0,
                        tenant="spans", prefill_chunk=16)
    gw = ServingGateway(eng, admission="fifo")
    prompts = [[(5 * i + j) % cfg.vocab_size for j in range(n)]
               for i, n in enumerate((9, 40, 12, 14))]

    def serve():
        for p in prompts:
            gw.submit(p, max_new_tokens=3)
        n = 0
        while gw.pending():
            gw.step()
            n += 1
        eng.flush_io(timeout=10.0)
        return n
    serve()                                   # compile every shape
    root = tmp_path_factory.mktemp("checkout")
    d = root / ".chipbench_trace" / CELL
    traced_before, steps_before = sum(TRACE_COUNTS.values()), eng.steps
    trace.start(d)
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        n_steps = serve()
    jax.profiler.stop_trace()
    shell.close()
    return SimpleNamespace(
        root=root, spans=spans.read(trace.find_xplane(d)), steps=n_steps,
        decodes=eng.steps - steps_before,
        retraced=sum(TRACE_COUNTS.values()) - traced_before)


def test_serving_spans_on_one_line_with_their_args(served):
    (lo, hi), line = spans.window(served.spans)
    inside = [s for s in served.spans if lo <= s.start and s.end <= hi]
    for name, args in SERVING.items():
        got = [s for s in inside if s.name == name]
        assert got, f"no {name} span"
        assert {s.line for s in got} == {line}, name
        assert all(set(s.args) == args for s in got), (name, got[0].args)
    by = lambda n: [s for s in inside if s.name == n]   # noqa: E731
    assert len(by("repro.engine.step")) == served.steps
    assert len(by("repro.engine.decode_dispatch")) == served.decodes
    assert {s.args["kind"] for s in by("repro.engine.prefill")} == \
        {"chunk", "shared"}
    assert {s.args["tag"] for s in by("repro.port.submit")} == \
        {"decode_io", "gateway_admit"}
    assert sum(s.args["admitted"] for s in by("repro.engine.admit")) == 4
    assert sum(s.args["freed"] for s in by("repro.mmu.extend")) == 4
    # the shell scheduler's worker records its spans on its own thread
    sched = [s for s in served.spans if s.name.startswith("repro.sched.")]
    assert sched and line not in {s.line for s in sched}
    assert all("intake" in s.args for s in sched
               if s.name == "repro.sched.issue")
    # spans live outside the jitted programs: tracing retraced nothing
    assert served.retraced == 0


def test_split_of_a_traced_cpu_run(served, monkeypatch):
    sp = spans.split(served.spans)
    assert sp.steps == served.decodes
    assert sp.device_wait_ms > 0 and sp.shell_host_ms > 0
    assert sp.engine_host_ms > 0 and sp.shell_thread_ms > 0
    assert sp.device_wait_ms + sp.shell_host_ms + sp.engine_host_ms == \
        pytest.approx(sp.step_ms)
    steps = [s for s in served.spans if s.name == "repro.engine.step"]
    mean = sum(s.end - s.start for s in steps) * 1e3 / sp.steps
    assert sp.step_ms == pytest.approx(mean)      # no step overlaps another
    (lo, hi), _ = spans.window(served.spans)
    by_self = spans.self_ms(served.spans)
    assert sum(by_self.values()) == pytest.approx((hi - lo) * 1e3 / sp.steps)
    # the metric files read the split of the run's own trace
    monkeypatch.setattr(spans, "ROOT", served.root)
    run = SimpleNamespace(trace=object(), cell=CELL)
    for m in METRICS:
        assert harness.load_reader(m)(run) == getattr(sp, m)
    # silent without a device trace, or without the run's trace file
    assert harness.load_reader(METRICS[0])(
        SimpleNamespace(trace=None, cell=CELL)) is None
    assert harness.load_reader(METRICS[0])(
        SimpleNamespace(trace=object(), cell="no-such-cell")) is None


def _hand_made():
    A, B, C = "host#0", "host#1", "host#2"
    return [
        Span("chipbench.window", A, 0.0, 10.0),
        Span("chipbench.step", A, 0.9, 4.1),
        Span("repro.engine.step", A, 1.0, 4.0),
        Span("repro.engine.step", A, 5.0, 9.0),
        Span("repro.engine.step", A, 9.5, 11.0),      # clipped at 10
        Span("repro.engine.decode_dispatch", A, 2.0, 2.1),
        Span("repro.engine.decode_dispatch", A, 6.0, 6.1),
        Span("repro.engine.decode_dispatch", A, 9.7, 9.8),
        Span("repro.engine.decode_dispatch", A, 10.2, 10.3),  # outside
        # waits overlap one another: counted once
        Span("repro.engine.decode_wait", A, 2.5, 3.5),
        Span("repro.engine.prefill_wait", A, 3.0, 3.8),
        Span("repro.engine.decode_wait", A, 6.5, 8.0),
        # shell spans: nested, overlapping a wait, and outside any step
        Span("repro.health.beat", A, 1.0, 1.2),
        Span("repro.port.submit", A, 3.6, 3.9),
        Span("repro.mmu.extend", A, 3.85, 3.95),
        Span("repro.mmu.block_table", A, 4.2, 4.4),
        Span("repro.sched.issue", A, 5.5, 5.6),        # serving thread
        # the shell's threads: overlapping across threads, clipped
        Span("repro.sched.issue", B, 1.0, 2.0),
        Span("repro.sched.issue", B, 1.5, 2.5),
        Span("repro.sched.execute", C, 2.0, 3.0),
        Span("repro.sched.issue", B, 9.8, 10.5),
    ]


def test_split_on_hand_made_spans():
    sp = spans.split(_hand_made())
    per = 1e3 / 3
    assert sp.steps == 3
    assert sp.step_ms == pytest.approx((3.0 + 4.0 + 0.5) * per)
    assert sp.device_wait_ms == pytest.approx((1.3 + 1.5) * per)
    assert sp.shell_host_ms == pytest.approx((0.2 + 0.15) * per)
    assert sp.engine_host_ms == pytest.approx((7.5 - 2.8 - 0.35) * per)
    assert sp.shell_thread_ms == pytest.approx((2.0 + 0.2) * per)
    assert sp.device_wait_ms + sp.shell_host_ms + sp.engine_host_ms == \
        pytest.approx(sp.step_ms)


def test_split_needs_a_window_and_program_spans():
    assert spans.split(_hand_made()[1:]) is None
    assert spans.split([s for s in _hand_made()
                        if not s.name.startswith("repro.")]) is None


def test_host_time_idle_time_and_gaps_go_to_the_innermost_span():
    got = spans.self_ms(_hand_made())
    assert sum(got.values()) == pytest.approx(10.0 * 1e3 / 3)
    assert got["repro.engine.decode_wait"] == pytest.approx(
        (0.5 + 1.5) * 1e3 / 3)
    # the wait's last 0.2 s lie inside the port and MMU spans
    assert got["repro.engine.prefill_wait"] == pytest.approx(0.6 * 1e3 / 3)
    assert got["chipbench.step"] == pytest.approx(0.2 * 1e3 / 3)
    serving = spans.serving(_hand_made())
    assert spans.innermost(serving, 3.87) == "repro.mmu.extend"
    assert spans.innermost(serving, 1.1) == "repro.health.beat"
    assert spans.innermost(serving, 4.05) == "chipbench.step"
    assert spans.innermost(serving, 4.5) == "no span"
    tr = trace.Trace(ops={"/device:TPU:0": [("a", 0.0, 1.0), ("b", 1.5, 3.2),
                                            ("c", 3.3, 4.3)]},
                     spans=[("chipbench.window", 0.0, 10.0)])
    idle = spans.idle_ms(tr, _hand_made())
    assert sum(idle.values()) == pytest.approx(6.3 * 1e3 / 3)
    assert idle["repro.engine.step"] == pytest.approx(3.0 * 1e3 / 3)
    assert idle["repro.engine.decode_wait"] == pytest.approx(1.5 * 1e3 / 3)
    assert idle["no span"] == pytest.approx(1.1 * 1e3 / 3)
    assert idle["repro.mmu.block_table"] == pytest.approx(0.1 * 1e3 / 3)
    assert spans.name_gaps(tr, _hand_made(), top=3) == [
        ("repro.engine.decode_wait", pytest.approx(5.7)),
        ("repro.engine.step", pytest.approx(0.5)),
        ("repro.engine.prefill_wait", pytest.approx(0.1))]


def test_interval_arithmetic():
    a = [(0.0, 2.0), (3.0, 5.0), (6.0, 7.0)]
    b = [(1.0, 3.5), (4.0, 4.5), (6.5, 9.0)]
    assert spans.intersect(a, b) == [(1.0, 2.0), (3.0, 3.5), (4.0, 4.5),
                                     (6.5, 7.0)]
    assert spans.subtract(a, b) == [(0.0, 1.0), (3.5, 4.0), (4.5, 5.0),
                                    (6.0, 6.5)]
    assert spans.subtract(a, []) == a and spans.intersect(a, []) == []
    assert spans.length(a) == pytest.approx(5.0)


def test_recorded_chip_trace_reduces_as_before():
    """The recorded trace predates the program's spans: the reduction
    reads the same numbers, and the gaps keep the benchmark's names."""
    tr = trace.read(SMALL)
    red = trace.reduce(tr)
    assert red.busy_s == pytest.approx(0.000722382999998071, rel=1e-9)
    assert red.window_s == pytest.approx(0.204874677, rel=1e-9)
    assert len(red.op_seconds) == 570
    assert sum(red.op_seconds.values()) == pytest.approx(
        0.0006675369999983652, rel=1e-9)
    assert red.program_runs == {
        "prefill_shared_paged": 3, "convert_element_type": 237, "less": 58,
        "add": 58, "select_n": 58, "scatter": 58, "prefill_chunk_paged": 2,
        "_squeeze": 4, "decode_step_paged": 4}
    assert [n for n, _ in red.gaps] == ["chipbench.step"] * 10
    assert [g for _, g in red.gaps] == pytest.approx(
        [0.004469097, 0.004460194, 0.00443214, 0.004193746, 0.003572872,
         0.003536749, 0.003461279, 0.003423557, 0.003379998, 0.003252703])
    sp = spans.read(SMALL)
    assert spans.name_gaps(tr, sp) == red.gaps
    cut = spans.segments(sp)
    assert sum(e - s for _, s, e in cut) == pytest.approx(red.window_s)
    idle = spans.intersect([(s, e) for _, s, e in cut], spans.holes(tr))
    assert spans.length(idle) == pytest.approx(red.window_s - red.busy_s)
    assert spans.split(sp) is None           # no program spans to split
