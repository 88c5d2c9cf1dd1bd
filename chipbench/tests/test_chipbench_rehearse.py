"""Whole runs of the harness on the CPU (``--rehearse``: reduced sizes,
interpret-mode kernels), in a temporary checkout that adds cells,
configurations, an architecture, traffic mixes and a metric as new
files, and as new entries (or cell names in a metric's list) in
``BENCHMARK.json``; no other file of the benchmark is edited."""
import json
import os
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
DATA = ROOT / "chipbench" / "tests" / "data"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402
from chipbench import traffic as T  # noqa: E402

SEED = "4000000007"            # wider than 32 bits, as benchmark seeds may be
KEYS = {"correct", "attempted", "failed", "metrics", "device"}
CELL = "phi3-decode-backlog"   # the benchmark's own cell
OPEN = ["smollm-chat-poisson", "smollm-shared-prefix"]     # open-loop cells
# a cell whose configuration names a throwaway architecture: the dense
# one with every count doubled
DOUBLED = "phi3-doubled-backlog"
DOUBLED_ARCH = '''from pathlib import Path

from chipbench.harness import load_arch

_dense = load_arch("dense", Path(__file__).resolve().parents[2])
dims_of, program_config = _dense.dims_of, _dense.program_config
make_weights, logits_at, gaps = (_dense.make_weights, _dense.logits_at,
                                 _dense.gaps)


def linear_flops(d):
    return 2 * _dense.linear_flops(d)


def head_flops(d):
    return 2 * _dense.head_flops(d)


def attn_flops(d, ctx):
    return 2 * _dense.attn_flops(d, ctx)


def prefill_attn_flops(d, start, n):
    return 2 * _dense.prefill_attn_flops(d, start, n)


def kernel_bytes(d, ctx_sum, rows):
    return 2 * _dense.kernel_bytes(d, ctx_sum, rows)
'''


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    for sub in ("archs", "configs", "traffic", "metrics"):
        shutil.copytree(ROOT / "chipbench" / sub, root / "chipbench" / sub)
    (root / "chipbench/archs/throwaway_doubled.py").write_text(DOUBLED_ARCH)
    conf = json.loads(
        (root / "chipbench/configs/phi3-medium-14b-8l.json").read_text())
    conf.update(name="phi3-doubled", arch="throwaway_doubled")
    (root / "chipbench/configs/phi3-doubled.json").write_text(
        json.dumps(conf))
    shutil.copy(DATA / "smollm-135m.json", root / "chipbench/configs")
    for name in OPEN:
        shutil.copy(DATA / f"{name}.json", root / "chipbench/traffic")
    mix = json.loads((DATA / "smollm-chat-poisson.json").read_text())
    mix["rate_per_s"] = 5.0
    (root / "chipbench/traffic/throwaway-mix.json").write_text(
        json.dumps(mix))
    (root / "chipbench/metrics/throwaway_requests.py").write_text(
        "def read(run):\n    return float(len(run.reqs))\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for name in ("smollm-135m", "phi3-doubled"):
        bench["configs"].append(
            {"name": name, "source": "test",
             "file": f"chipbench/configs/{name}.json", "reduced": []})
    bench["workloads"].append(
        {"name": DOUBLED, "config": "phi3-doubled", "traffic": CELL,
         "chips": 1, "why": "test"})
    for name in OPEN + ["throwaway-cell"]:
        bench["workloads"].append(
            {"name": name, "config": "smollm-135m",
             "traffic": "throwaway-mix" if name == "throwaway-cell" else name,
             "chips": 1, "why": "test"})
    for name in ("ttft_p95_ms", "itl_p95_ms"):
        bench["end_to_end"].append(
            {"name": name, "unit": "ms", "better": "lower", "bound": 0.25,
             "source": "host_clock", "workloads": OPEN})
    bench["end_to_end"].append(
        {"name": "throwaway_requests", "unit": "requests", "better": "higher",
         "bound": 0.01, "source": "host_clock",
         "workloads": ["throwaway-cell"]})
    for name, cells in (("prefix_hit_share", OPEN[1:]),
                        ("gateway_wait_ms", OPEN)):
        bench["per_layer"].append(
            {"name": name, "unit": "%", "better": "higher",
             "source": "program_counter", "layer": "test",
             "moves": "ttft_p95_ms", "workloads": cells})
    for m in bench["per_layer"]:
        if m["name"] == "engine_step_ms":
            m["workloads"] += OPEN
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run(root, capsys, workload, *extra, trace=0):
    rc = harness.main(["--workload", workload, "--seed", SEED,
                       "--seconds", "3", "--trace", str(trace),
                       "--rehearse", *extra], root=root)
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)


def test_new_cell_and_metric_run_from_files_alone(checkout, capsys):
    rc, res = run(checkout, capsys, "throwaway-cell")
    assert rc == 0 and KEYS <= set(res)
    assert list(res)[-1] == "compared"
    assert res["correct"] is True, res["compared"]
    # 3 s at 5 requests/s are judged; the 1 s lead-in adds 5 unjudged
    assert res["failed"] == 0 and res["attempted"] == 15
    # the tails list their cells, and the new cell is not among them
    assert set(res["metrics"]) == {"setup_s", "tokens_per_s",
                                   "throwaway_requests"}
    assert res["metrics"]["throwaway_requests"]["value"] == 20.0
    assert res["device"]["platform"] == "cpu"


def test_open_loop_cell_reports_its_tails(checkout, capsys):
    rc, res = run(checkout, capsys, "smollm-chat-poisson")
    assert rc == 0 and res["correct"] is True, res["compared"]
    assert set(res["metrics"]) == {"setup_s", "tokens_per_s", "ttft_p95_ms",
                                   "itl_p95_ms"}
    assert res["attempted"] == 8                     # 2.5 requests/s, 3 s


def test_traced_run_reports_host_side_layers(checkout, capsys):
    rc, res = run(checkout, capsys, "smollm-shared-prefix", trace=1)
    assert rc == 0 and res["correct"] is True, res["compared"]
    got = set(res["metrics"])
    # on the CPU there is no device trace: device metrics stay silent
    assert {"prefix_hit_share", "engine_step_ms", "gateway_wait_ms"} <= got
    assert not got & {"decode_device_ms", "idle_share", "mfu",
                      "paged_attn_roofline"}
    assert res["metrics"]["prefix_hit_share"]["value"] > 0


def test_traced_backlog_run_reports_engine_step(checkout, capsys):
    rc, res = run(checkout, capsys, CELL, trace=1)
    assert rc == 0 and res["correct"] is True, res["compared"]
    assert set(res["metrics"]) == {"engine_step_ms"}
    assert "busy_s" not in res["device"]


@pytest.mark.parametrize("fault", ["token", "state"])
def test_broken_path_is_not_correct(checkout, capsys, fault):
    rc, res = run(checkout, capsys, CELL, "--fault", fault)
    assert rc == 0 and res["correct"] is False
    gap = res["compared"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_no_tpu_no_result(checkout, capsys):
    rc = harness.main(["--workload", CELL, "--seed", "1",
                       "--seconds", "1", "--trace", "0"], root=checkout)
    assert rc != 0 and capsys.readouterr().out.strip() == ""


def test_float8_control_fails_the_limit(checkout, capsys):
    """The control: the reference one precision below the stated bf16
    (float8 matmul inputs), its first tokens judged where the program's
    were, must come out not correct, while the program's own tokens meet
    the same limit."""
    rc, res = run(checkout, capsys, CELL, "--control")
    assert rc == 0 and res["correct"] is False
    ctrl = res["compared"]["max_logit_gap"]
    assert ctrl["value"] > ctrl["limit"]
    prog = res["compared"]["program_max_logit_gap"]
    assert prog["value"] <= prog["limit"]


def test_new_architecture_runs_from_files_alone(checkout, capsys):
    assert harness.load_cell(DOUBLED, False, checkout).arch.__name__ == \
        "chipbench_archs_throwaway_doubled"
    rc, res = run(checkout, capsys, DOUBLED)
    assert rc == 0 and res["correct"] is True, res["compared"]
    assert set(res["metrics"]) == {"setup_s", "tokens_per_s"}


def traced_run(checkout, cell):
    """One synthetic traced run of ``cell``: two decode steps and one
    prefill call inside the traced part, one decode step outside it."""
    spec = harness.load_cell(cell, True, checkout)
    run = harness.Run(cell=cell, arrivals="backlog", arch=spec.arch,
                      dims=spec.arch.dims_of(spec.conf),
                      device_kind="TPU v5 lite",
                      t_trace0=10.0, t_trace1=13.0)
    req = harness.Req(arrival=T.Arrival(due=0.0, prompt=[1] * 700,
                                        max_new=8), due=0.0)
    req.stamps = [9.0, 9.5, 11.0, 12.0]
    run.reqs.append(req)
    run.prefill_calls.append(("shared", 10.5, np.array([0, 96]),
                              np.array([512, 64])))
    run.trace = SimpleNamespace(window_s=3.0, op_seconds={
        '%paged_decode.7 custom-call custom_call_target="tpu_custom_call"':
            0.002, "%fusion.1 fusion": 0.5})
    return run


@pytest.mark.parametrize("metric", ["mfu", "paged_attn_roofline"])
def test_readers_take_counts_from_the_architecture(checkout, metric):
    read = harness.load_reader(metric, checkout)
    dense = read(traced_run(checkout, CELL))
    assert dense > 0
    assert read(traced_run(checkout, DOUBLED)) == 2 * dense
