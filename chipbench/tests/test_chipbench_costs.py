"""FLOP and byte counts against hand arithmetic, and the peaks table."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import costs, harness  # noqa: E402

dense = harness.load_arch("dense")

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def smollm():
    return dense.dims_of(json.loads((DATA / "smollm-135m.json").read_text()))


def test_smollm_linear_and_head_flops(smollm):
    # per layer: q 576x576, k and v 576x192, o 576x576, SwiGLU 3x576x1536
    per_layer = 331776 + 110592 + 110592 + 331776 + 2654208
    assert dense.linear_flops(smollm) == 2 * 30 * per_layer == 212336640
    assert dense.head_flops(smollm) == 2 * 576 * 49152 == 56623104


def test_smollm_attention_flops(smollm):
    # q.k and p.v: 2 products x 2 FLOPs x 9 heads x 64 x keys x 30 layers
    assert dense.attn_flops(smollm, 1000) == 4 * 30 * 9 * 64 * 1000
    # queries at positions 5..8 see 6, 7, 8 and 9 keys
    assert dense.prefill_attn_flops(smollm, 5, 4) == \
        dense.attn_flops(smollm, 6 + 7 + 8 + 9)


def test_smollm_kernel_bytes(smollm):
    # K and V of 1000 tokens (3 kv heads x 64) and q, o of 2 rows (9 x 64),
    # bfloat16, 30 layers
    want = 30 * (2 * 1000 * 3 * 64 + 2 * 2 * 9 * 64) * 2
    assert dense.kernel_bytes(smollm, 1000, 2) == want == 23178240


def test_phi3_sizes():
    d = dense.dims_of(
        json.loads((CONFIGS / "phi3-medium-14b-8l.json").read_text()))
    assert (d.layers, d.d, d.h, d.kv, d.hd, d.ff, d.vocab) == \
        (8, 5120, 40, 10, 128, 17920, 32064)
    assert d.padded_vocab == 32128 and not d.tied


def test_peaks_known_and_unknown():
    v5e = costs.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        costs.peaks("TPU v99 imaginary")
