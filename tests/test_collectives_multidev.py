"""Multi-device validation of the collective service (hierarchical
all-reduce) and the context-parallel decode attention.

Runs in a SUBPROCESS with 8 forced host devices — the main test process
must keep seeing exactly 1 CPU device (dry-run rule)."""
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from jax.sharding import AxisType

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)

    # ---- hierarchical all-reduce == flat psum -----------------------------
    from repro.core.services.collectives import CollectiveService, CollectiveConfig
    svc = CollectiveService(CollectiveConfig(schedule="hierarchical"))
    x = jnp.arange(32.0).reshape(8, 4)

    def flat(v):
        return jax.lax.psum(v, ("pod", "data"))

    def hier(v):
        return svc.all_reduce(v, mesh)

    f = jax.shard_map(flat, mesh=mesh, in_specs=P(("pod", "data"), None),
                      out_specs=P(None, None), check_vma=False)
    h = jax.shard_map(hier, mesh=mesh, in_specs=P(("pod", "data"), None),
                      out_specs=P(None, None), check_vma=False)
    a, b = np.asarray(f(x)), np.asarray(h(x))
    assert np.allclose(a, b, atol=1e-5), (a, b)

    # ---- context-parallel decode attention == dense reference -------------
    from repro.models.attention import attend_decode, attend_decode_cp
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    B, S, H, K, D = 4, 16, 4, 2, 8
    q = jax.random.normal(ks[0], (B, 1, H, D))
    kc = jax.random.normal(ks[1], (B, S, K, D))
    vc = jax.random.normal(ks[2], (B, S, K, D))
    lens = jnp.array([16, 9, 12, 5], jnp.int32)
    ref = attend_decode(q, kc, vc, lens)
    with mesh:
        qd = jax.device_put(q, jax.NamedSharding(mesh, P("data")))
        kd = jax.device_put(kc, jax.NamedSharding(mesh, P("data", "model")))
        vd = jax.device_put(vc, jax.NamedSharding(mesh, P("data", "model")))
        ld = jax.device_put(lens, jax.NamedSharding(mesh, P("data")))
        out = jax.jit(lambda *a: attend_decode_cp(
            *a, mesh, batch_axes=("data",)))(qd, kd, vd, ld)
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err < 1e-4, err
    print("MULTIDEV_OK")
""")


@pytest.mark.slow
@pytest.mark.parametrize("rep", [0])
def test_hierarchical_ar_and_cp_attention(rep):
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=300,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root",
                            # forced-host-device scripts are CPU-only; an
                            # unpinned platform probes for TPUs (minutes of
                            # metadata-server retries in some containers)
                            "JAX_PLATFORMS": "cpu"})
    assert "MULTIDEV_OK" in r.stdout, f"\nstdout:{r.stdout}\nstderr:{r.stderr[-2000:]}"
