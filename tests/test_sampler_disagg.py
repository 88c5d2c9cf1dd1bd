"""Sampler suite + prefill/decode disaggregation hand-off."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve.sampler import SamplerConfig, sample


def test_greedy_matches_argmax():
    logits = jax.random.normal(jax.random.PRNGKey(0), (4, 100))
    out = sample(jax.random.PRNGKey(1), logits, SamplerConfig())
    np.testing.assert_array_equal(np.asarray(out),
                                  np.argmax(np.asarray(logits), -1))


def test_top_k_restricts_support():
    logits = jnp.asarray(np.random.RandomState(0).randn(2000, 50))
    cfg = SamplerConfig(temperature=1.0, top_k=3)
    toks = np.asarray(sample(jax.random.PRNGKey(2), logits, cfg))
    top3 = np.argsort(np.asarray(logits), -1)[:, -3:]
    assert all(t in row for t, row in zip(toks, top3))


def test_top_p_keeps_at_least_one_and_restricts():
    # peaked distribution: nucleus p=0.5 must keep only the top token
    logits = jnp.asarray([[10.0, 0.0, 0.0, 0.0]] * 200)
    cfg = SamplerConfig(temperature=1.0, top_p=0.5)
    toks = np.asarray(sample(jax.random.PRNGKey(3), logits, cfg))
    assert (toks == 0).all()


def test_min_p_filters_tail():
    logits = jnp.asarray([[5.0, 4.9, -10.0, -10.0]] * 500)
    cfg = SamplerConfig(temperature=1.0, min_p=0.5)
    toks = np.asarray(sample(jax.random.PRNGKey(4), logits, cfg))
    assert set(np.unique(toks)) <= {0, 1}


def test_temperature_spreads():
    logits = jnp.asarray([[2.0, 1.5, 1.0, 0.5]] * 2000)
    cold = np.asarray(sample(jax.random.PRNGKey(5), logits,
                             SamplerConfig(temperature=0.1)))
    hot = np.asarray(sample(jax.random.PRNGKey(5), logits,
                            SamplerConfig(temperature=5.0)))
    assert len(np.unique(cold)) <= len(np.unique(hot))


DISAGG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from jax.sharding import AxisType
    from repro.serve.disaggregated import make_handoff_fn, handoff_wire_bytes

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)
    handoff, qp = make_handoff_fn(mesh)
    # dim0 pod-sharded: rows 0-1 = prefill pod KV, rows 2-3 = decode pool
    cache = {"k": jnp.arange(4 * 6, dtype=jnp.float32).reshape(4, 6),
             "v": -jnp.arange(4 * 6, dtype=jnp.float32).reshape(4, 6)}
    with mesh:
        dev = jax.device_put(cache, jax.tree.map(
            lambda _: jax.NamedSharding(mesh, P("pod")), cache))
        out = jax.jit(handoff)(dev)
    k = np.asarray(out["k"])
    np.testing.assert_array_equal(k[2:], np.asarray(cache["k"])[:2])  # delivered
    np.testing.assert_array_equal(k[:2], np.asarray(cache["k"])[:2])  # kept
    assert handoff_wire_bytes(cache) == sum(
        x.nbytes for x in cache.values()) / 2
    print("DISAGG_OK")
""")


@pytest.mark.slow
def test_disaggregated_handoff_multidev():
    r = subprocess.run([sys.executable, "-c", DISAGG], capture_output=True,
                       text=True, timeout=300,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root",
                            # see test_collectives_multidev: pin to CPU so
                            # the child never probes for TPU backends
                            "JAX_PLATFORMS": "cpu"})
    assert "DISAGG_OK" in r.stdout, f"\n{r.stdout}\n{r.stderr[-2000:]}"


# ---------------------- per-row top-k/top-p in the fused sampler ----------
def test_sample_per_row_topk1_is_exactly_greedy_even_hot():
    from repro.serve.sampler import sample_per_row
    logits = jax.random.normal(jax.random.PRNGKey(1), (6, 50)) * 3.0
    temps = jnp.full((6,), 5.0)
    tk = jnp.asarray([1, 0, 1, 3, 1, 0], jnp.int32)
    tp = jnp.ones((6,), jnp.float32)
    am = np.argmax(np.asarray(logits), -1)
    top3 = np.argsort(np.asarray(logits), -1)[:, -3:]
    for s in range(8):
        toks = np.asarray(sample_per_row(jax.random.PRNGKey(s), logits,
                                         temps, tk, tp))
        np.testing.assert_array_equal(toks[[0, 2, 4]], am[[0, 2, 4]])
        assert toks[3] in top3[3]               # row-local k=3 support


def test_sample_per_row_per_row_top_p():
    from repro.serve.sampler import sample_per_row
    # row 0 peaked + p=0.5 -> must collapse to the top token;
    # row 1 flat + p=1.0 -> unrestricted
    lg = jnp.asarray([[10.0, 0.0, 0.0, 0.0], [0.1, 0.2, 0.15, 0.12]])
    tp = jnp.asarray([0.5, 1.0], jnp.float32)
    tk = jnp.zeros((2,), jnp.int32)
    seen1 = set()
    for s in range(24):
        t = np.asarray(sample_per_row(jax.random.PRNGKey(s), lg,
                                      jnp.full((2,), 1.0), tk, tp))
        assert t[0] == 0
        seen1.add(int(t[1]))
    assert len(seen1) > 1                        # row 1 still samples


def test_sample_per_row_disabled_filters_match_legacy_path():
    from repro.serve.sampler import sample_per_row
    logits = jax.random.normal(jax.random.PRNGKey(2), (4, 40))
    temps = jnp.full((4,), 1.3)
    a = np.asarray(sample_per_row(jax.random.PRNGKey(7), logits, temps))
    b = np.asarray(sample_per_row(jax.random.PRNGKey(7), logits, temps,
                                  jnp.zeros((4,), jnp.int32),
                                  jnp.ones((4,), jnp.float32)))
    np.testing.assert_array_equal(a, b)


def test_host_oracle_matches_fused_support_restriction():
    """The engine's host Gumbel oracle stays in parity with the fused
    sampler: same top-k/top-p support rule on the same logits."""
    from repro.configs import get_config
    from repro.core.services.mmu import MMU, MMUConfig
    from repro.serve.engine import ServingEngine
    cfg = get_config("smollm-135m").reduced()
    eng = ServingEngine.__new__(ServingEngine)   # oracle only, no model
    eng.cfg = cfg
    eng._rng = np.random.RandomState(0)
    v = cfg.vocab_size
    logits = np.random.RandomState(1).randn(200, v) * 3.0
    toks = eng._sample(logits, 1.0, top_k=3)
    top3 = np.argsort(logits, -1)[:, -3:]
    assert all(t in row for t, row in zip(toks, top3))
    # top_k=1 == greedy exactly
    np.testing.assert_array_equal(eng._sample(logits, 5.0, top_k=1),
                                  np.argmax(logits, -1))
    # peaked distribution under p=0.5 keeps only the head
    peak = np.zeros((50, v), np.float32)
    peak[:, 7] = 12.0
    assert (eng._sample(peak, 1.0, top_p=0.5) == 7).all()
