"""The dense architecture module against values recorded before the
model-specific code moved into ``chipbench/archs/``: the same weights
bit for bit from the same seed, the same reference logits, the same
counts.  A reading that moves here would move the benchmark's numbers."""
import hashlib
import json
import os
import sys
from pathlib import Path

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import harness  # noqa: E402

SEED = 4000000007              # wider than 32 bits, as benchmark seeds may be
PHI3 = json.loads(
    (ROOT / "chipbench/configs/phi3-medium-14b-8l.json").read_text())


@pytest.fixture(scope="module")
def dense():
    return harness.load_arch("dense")


def digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_configuration_without_arch_key_is_dense(dense):
    assert "arch" not in PHI3
    cell = harness.load_cell("phi3-decode-backlog", False)
    assert cell.arch is dense


@pytest.mark.parametrize("dtype,want", [
    (jnp.bfloat16,
     "07a8c91775d27e6f9ffac98391384684af6757988365666dbbf2f8d8482ec5aa"),
    (jnp.float32,
     "af03b36f879104c5b81571a2b4f13f073ea4e7549e2f55f606d7c7f8496bd9b6")],
    ids=["bfloat16", "float32"])
def test_dense_weights_pinned(dense, dtype, want):
    dims = dense.dims_of(PHI3, rehearse=True)
    assert digest(dense.make_weights(dims, SEED, dtype=dtype)) == want


def test_dense_reference_logits_pinned(dense):
    dims = dense.dims_of(PHI3, rehearse=True)
    w = dense.make_weights(dims, SEED, dtype=jnp.float32)
    toks = np.random.default_rng(11).integers(0, dims.vocab, size=40)
    lg = dense.logits_at(w, dims, toks.tolist(), [0, 17, 39], pad_to=64)
    assert lg.dtype == np.float32 and lg.shape == (3, 512)
    assert lg.argmax(-1).tolist() == [203, 156, 362]
    assert lg[:, 0].tolist() == [0.43566635251045227, -1.1953942775726318,
                                 -0.6858463287353516]
    assert hashlib.sha256(lg.tobytes()).hexdigest() == \
        "d9ff75ee88fc43c80a9a2ee5d8084727cefe75a48715c250b235dfea52dcec1a"


def test_dense_counts_pinned(dense):
    d = dense.dims_of(PHI3)
    assert dense.linear_flops(d) == 5452595200
    assert dense.head_flops(d) == 328335360
    assert dense.attn_flops(d, 1000) == 163840000
    assert dense.prefill_attn_flops(d, 5, 4) == 4915200
    assert dense.kernel_bytes(d, 1000, 2) == 41287680
