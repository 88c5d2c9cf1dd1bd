"""Whole runs of the harness on the CPU (``--rehearse``: reduced sizes,
interpret-mode kernels), in a temporary checkout that adds cells, a
configuration, traffic mixes and a metric as new files, and as new
entries (or cell names in a metric's list) in ``BENCHMARK.json``; no other
file of the benchmark is edited."""
import json
import os
import shutil
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
DATA = ROOT / "chipbench" / "tests" / "data"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402

SEED = "4000000007"            # wider than 32 bits, as benchmark seeds may be
KEYS = {"correct", "attempted", "failed", "metrics", "device"}
CELL = "phi3-decode-backlog"   # the benchmark's own cell
OPEN = ["smollm-chat-poisson", "smollm-shared-prefix"]     # open-loop cells


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(ROOT / "chipbench" / sub, root / "chipbench" / sub)
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
    bench["configs"].append(
        {"name": "smollm-135m", "source": "test",
         "file": "chipbench/configs/smollm-135m.json", "reduced": []})
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
