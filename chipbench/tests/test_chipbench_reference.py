"""The plain reference against the serving path's own programs at the
reduced sizes: one-shot prefill into scattered pages, then decode steps
through the block tables, compared logit by logit."""
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

dense = harness.load_arch("dense")

PAGE, N_PAGES, MAX_LEN, STEPS = 16, 64, 128, 4
CONFIG_FILES = {
    "smollm-135m": ROOT / "chipbench/tests/data/smollm-135m.json",
    "phi3-medium-14b-8l": ROOT / "chipbench/configs/phi3-medium-14b-8l.json"}


def serve_logits(cfg, params, prompts):
    """Decode logits of the program's paged path after each prompt and
    the tokens it picks greedily; returns (tokens, logits per step)."""
    from repro.serve.paged_model import (decode_logits_paged, make_pools,
                                         prefill_shared_paged)
    n, maxp = len(prompts), MAX_LEN // PAGE
    lens = np.array([len(p) for p in prompts], np.int32)
    tables = np.random.RandomState(0).permutation(N_PAGES)[:n * maxp]
    tables = jnp.asarray(tables.reshape(n, maxp).astype(np.int32))
    toks = np.zeros((n, 64), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    zeros = jnp.zeros((n,), jnp.int32)
    first, pools, _ = prefill_shared_paged(
        params, make_pools(cfg, N_PAGES, PAGE), jnp.asarray(toks),
        jnp.asarray(lens), zeros, zeros, tables, jax.random.PRNGKey(0),
        jnp.zeros((n,), jnp.float32), cfg=cfg, page_size=PAGE)
    out, logits = [np.asarray(first)], []
    cur, last = jnp.asarray(lens), first
    for _ in range(STEPS):
        lg, pools = decode_logits_paged(params, pools, tables, cur, last,
                                        cfg=cfg, page_size=PAGE)
        logits.append(np.asarray(lg))
        last = jnp.asarray(np.asarray(lg).argmax(-1).astype(np.int32))
        out.append(np.asarray(last))
        cur = cur + 1
    return np.stack(out, 1), logits


@pytest.mark.parametrize("config", ["smollm-135m", "phi3-medium-14b-8l"])
def test_reference_matches_serving_path(config):
    conf = json.loads(CONFIG_FILES[config].read_text())
    dims = dense.dims_of(conf, rehearse=True)
    cfg = dense.program_config(config, dims)
    # float32 weights: both sides then compute in float32 on the CPU, so
    # they differ only by summation order
    w = dense.make_weights(dims, 7, dtype=jnp.float32)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, dims.vocab, size=n).tolist() for n in (20, 37)]
    toks, logits = serve_logits(cfg, w, prompts)
    for i, p in enumerate(prompts):
        seq = p + toks[i].tolist()
        rows = np.arange(len(p), len(p) + STEPS)
        ref = dense.logits_at(w, dims, seq[:len(p) + STEPS], rows,
                                  pad_to=MAX_LEN)
        got = np.stack([lg[i] for lg in logits])
        scale = np.abs(ref).max()
        assert np.abs(got - ref).max() / scale < 1e-4
        first = dense.logits_at(w, dims, p, [len(p) - 1],
                                pad_to=MAX_LEN)[0]
        assert toks[i, 0] == first.argmax()


def test_gaps_zero_for_reference_tokens_and_control_reads_higher():
    conf = json.loads(CONFIG_FILES["smollm-135m"].read_text())
    dims = dense.dims_of(conf, rehearse=True)
    w = dense.make_weights(dims, 5)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, dims.vocab, size=40).tolist()
    served = []
    for _ in range(24):            # the reference's own greedy tokens
        lg = dense.logits_at(w, dims, prompt + served,
                             [len(prompt) + len(served) - 1],
                             pad_to=MAX_LEN)[0]
        served.append(int(lg.argmax()))
    prog, ctrl = dense.gaps(w, dims, prompt, served, pad_to=MAX_LEN,
                            control=True)
    assert prog.shape == (24,) and float(prog.max()) == 0.0
    assert float(ctrl.max()) > 0.0
