"""Compile the serving hot path for a TPU v5e that is described, not
attached.

The TPU compiler ships with jaxlib, so these compiles run without a chip
and refuse what interpret mode accepts: Mosaic block tiling, VMEM limits,
programs that do not fit.  The topology is described inside a fixture
(never at import time): only the worker that runs this file loads the
TPU library, and every worker collects the same tests.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

import repro.kernels
from repro.configs import get_config
from repro.kernels.paged_attention.paged_attention import paged_attention
from repro.models import transformer as T
from repro.serve import paged_model

V5E_HBM_BYTES = 16 * 1024 ** 3
BATCH = 8


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # no compiler logs on disk
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure: no TPU here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield topo


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("page", [16, 32])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_paged_attention_compiles_for_v5e(one_chip, no_persistent_cache,
                                          dtype, page):
    """The paged decode kernel at smollm-135m widths (9 q / 3 kv heads,
    head_dim 64) compiles to a Mosaic kernel for one v5e chip."""
    cfg = get_config("smollm-135m")
    kh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    n_pages, max_pages = 512, 256 // page
    args = _on(one_chip, (
        jax.ShapeDtypeStruct((BATCH, cfg.n_heads, hd), dtype),
        jax.ShapeDtypeStruct((n_pages, kh, page, hd), dtype),
        jax.ShapeDtypeStruct((n_pages, kh, page, hd), dtype),
        jax.ShapeDtypeStruct((BATCH, max_pages), jnp.int32),
        jax.ShapeDtypeStruct((BATCH,), jnp.int32)))
    compiled = jax.jit(
        lambda *a: paged_attention(*a, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_full_width_decode_step_compiles_for_v5e(one_chip,
                                                 no_persistent_cache,
                                                 monkeypatch):
    """The whole single-device decode step of smollm-135m at its
    published widths, float32 as served, compiles for one v5e chip with
    the Pallas kernel inside and fits the chip's memory."""
    # the step picks its kernel mode from the backend, which is the CPU
    # here: steer it to what it picks on a TPU
    monkeypatch.setattr(repro.kernels, "on_tpu", lambda: True)
    cfg = get_config("smollm-135m")
    page, n_pages, max_pages = 32, 256, 8
    params = _on(one_chip, jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg,
                              dtype=jnp.float32)))
    pools = _on(one_chip, jax.eval_shape(
        lambda: paged_model.make_pools(cfg, n_pages, page)))
    rng = _on(one_chip, jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    i32, f32 = jnp.int32, jnp.float32
    vec = [_on(one_chip, jax.ShapeDtypeStruct((BATCH,), t))
           for t in (i32, i32, f32, i32, f32, i32)]
    tables = _on(one_chip, jax.ShapeDtypeStruct((BATCH, max_pages), i32))
    lens, last, temps, top_k, top_p, seq_ids = vec
    compiled = paged_model.decode_step_paged.lower(
        params, pools, tables, lens, last, rng, temps, top_k, top_p,
        seq_ids, cfg=cfg, page_size=page).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES, mem
