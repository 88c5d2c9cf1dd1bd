"""The trace reduction on a small trace recorded on one v5e chip
(``record_trace.py``), and on hand-made intervals."""
import os
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import trace  # noqa: E402

SMALL = Path(__file__).parent / "data" / "small_trace.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    return trace.read(SMALL)


def test_union_merges_and_clips():
    got = trace.union([(5, 6), (0, 2), (1, 3), (8, 12)], 0.5, 10)
    assert got == [(0.5, 3), (5, 6), (8, 10)]
    assert trace.union([(0, 1)], 2, 3) == []


def test_labels_and_program_names():
    assert trace.program_name("jit_decode_step_paged(123)") == \
        "decode_step_paged"
    assert trace.op_label("%fusion.3 = f32[8]{0:T(128)} fusion(f32[8] %a),"
                          " kind=kLoop") == "%fusion.3 fusion"
    assert trace.op_label(
        "%copy-start = (s32[2]{0}, u32[]) copy-start(s32[2]{0} %x)") == \
        "%copy-start copy-start"


def test_recorded_trace_has_device_ops_programs_and_spans(recorded):
    assert list(recorded.ops) == ["/device:TPU:0"]
    names = {trace.program_name(n) for n, _, _ in
             recorded.modules["/device:TPU:0"]}
    assert {"decode_step_paged", "prefill_shared_paged",
            "prefill_chunk_paged"} <= names
    spans = {n for n, _, _ in recorded.spans}
    assert {"chipbench.window", "chipbench.submit",
            "chipbench.step"} <= spans


def test_recorded_trace_reduces(recorded):
    red = trace.reduce(recorded)
    lo, hi = trace.window_of(recorded)
    assert red.window_s == pytest.approx(hi - lo)
    assert 0 < red.busy_s < red.window_s
    # leaf ops run one at a time inside the busy union (a scan's while
    # op encloses its body's ops and is not counted as an op itself)
    assert 0 < sum(red.op_seconds.values()) <= red.busy_s + 1e-12
    assert not any(" while(" in n for n in red.op_seconds)
    assert red.program_runs["decode_step_paged"] >= 2
    kernel = [n for n in red.op_seconds if "tpu_custom_call" in n]
    assert kernel, "the Pallas kernel shows as a Mosaic custom call"
    assert 0 < sum(red.op_seconds[n] for n in kernel) < red.busy_s
    # every idle gap is named by the span the host was in, longest first
    assert red.gaps and all(n.startswith("chipbench.") or n == "no span"
                            for n, _ in red.gaps)
    lens = [s for _, s in red.gaps]
    assert lens == sorted(lens, reverse=True)
    assert sum(lens) <= red.window_s - red.busy_s + 1e-9
    top = trace.top_ops(red, 5)
    assert len(top) == 5 and top[0][1] >= top[-1][1] > 0
