"""Mesh-sharded paged serving: tensor-parallel engine parity.

The acceptance pin of the sharding PR: a ``ServingEngine`` given a mesh
with ``model > 1`` produces EXACTLY the tokens the single-device engine
produces — greedy and sampled rows, through admission churn, eviction /
fault-back-in, live migration and in-place slot recovery.  Logits differ
in the last ulp across TP degrees (float reduction order), tokens must
not.

Multi-device runs happen in SUBPROCESSES with forced host devices — the
main test process must keep seeing exactly 1 CPU device (dry-run rule,
tests/conftest.py).  The in-process tests cover the pure-Python policy
pieces (MeshRules, tp_plan, make_host_mesh errors).
"""
import dataclasses
import subprocess
import sys
import textwrap

import pytest

from repro.configs import get_config
from repro.launch.mesh import make_host_mesh
from repro.models.sharding import MeshRules
from repro.serve.tp import tp_plan

_ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
        # forced-host-device scripts are CPU-only; an unpinned platform
        # probes for TPUs (minutes of metadata-server retries)
        "JAX_PLATFORMS": "cpu"}


def _run_sub(script: str, ok: str):
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=540, env=_ENV)
    for line in r.stdout.splitlines():
        if line.startswith("SKIP:"):
            pytest.skip(line[5:].strip())
    assert ok in r.stdout, \
        f"\nstdout:{r.stdout}\nstderr:{r.stderr[-3000:]}"


# A shared preamble: force 4 host devices, build mesh or print SKIP with
# the make_host_mesh RuntimeError message (the descriptive-error
# satellite — tests skip on it rather than erroring).
_PREAMBLE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.core.services.mmu import MMU, MMUConfig
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as T
    from repro.serve.engine import ServingEngine
    from repro.serve.paged_model import flat_page_indices, gather_kv_pages

    def mesh_or_skip(data, model):
        try:
            return make_host_mesh(data, model)
        except RuntimeError as e:
            print("SKIP:", e)
            raise SystemExit(0)

    def drain(*engines):
        for eng in engines:
            while eng.pending():
                eng.step()

    def tokens(eng):
        return {r.rid: list(r.out_tokens) for r in eng.completed}
""")


# ================================================ in-process (1 device) ====
def test_meshrules_unknown_scheme_raises():
    with pytest.raises(ValueError, match="unknown MeshRules scheme"):
        MeshRules.from_mesh(None, "diagonal")   # checked before mesh use


def test_meshrules_tp_divisibility_degrades_to_replication():
    rules = MeshRules(fsdp_axes=("data",), tp_axis="model", fsdp_size=0,
                      tp_size=3)
    assert rules.tp(6) == "model"       # divisible -> sharded
    assert rules.tp(7) is None          # not divisible -> replicated
    assert rules.tp(0) == "model"       # 0 % n == 0 (empty dim edge)
    serving = rules.serving()
    assert serving.shard_params_fsdp is False
    assert serving.tp(6) == "model"     # TP survives serving mode
    assert serving.fsdp(6) is None      # FSDP rows do not


def test_tp_plan_static_degradation():
    cfg = get_config("smollm-135m").reduced()   # 4 q / 2 kv heads, silu
    assert tp_plan(cfg, 2) == {"shard_heads": True, "shard_mlp": True}
    # kv heads (2) don't divide 4 -> attention replicates, MLP still shards
    assert tp_plan(cfg, 4) == {"shard_heads": False, "shard_mlp": True}
    assert tp_plan(cfg, 1) == {"shard_heads": False, "shard_mlp": False}
    # GELU applies b_down pre-reduction -> MLP must replicate
    gelu = dataclasses.replace(cfg, act="gelu")
    assert not tp_plan(gelu, 2)["shard_mlp"]
    # indivisible hidden dim -> MLP replicates
    odd = dataclasses.replace(cfg, d_ff=250)
    assert not tp_plan(odd, 4)["shard_mlp"]


def test_make_host_mesh_raises_descriptive_not_assert():
    """Single-device process asking for a 4-device mesh gets a
    RuntimeError naming the XLA_FLAGS fix, never a bare assert."""
    with pytest.raises(RuntimeError,
                       match="xla_force_host_platform_device_count=4"):
        make_host_mesh(1, 4)


# ================================================== subprocess (4 dev) ====
@pytest.mark.slow
def test_tp2_token_parity_under_churn_and_eviction():
    """TP=2 engine vs single-device engine: identical token streams with
    greedy AND sampled rows, slot churn (more requests than slots), and
    evict-with-copy byte-exactness on the sharded pools."""
    script = _PREAMBLE + textwrap.dedent("""
        mesh = mesh_or_skip(1, 2)
        cfg = get_config("smollm-135m").reduced()
        params = T.init_params(jax.random.PRNGKey(0), cfg,
                               dtype=jnp.float32)

        def build(mesh):
            mmu = MMU(MMUConfig(page_size=16, n_pages=128))
            return ServingEngine(cfg, params, mmu, max_batch=2,
                                 max_len=96, seed=0, mesh=mesh)

        single, tp2 = build(None), build(mesh)
        assert tp2.tp is not None and tp2.tp.shard_heads \\
            and tp2.tp.shard_mlp
        # local shard of the KV pool holds kv_heads // 2 heads
        local = tp2.pools["k"].addressable_shards[0].data.shape
        assert local[1] == cfg.n_kv_heads // 2, local
        # 5 requests through 2 slots: admission churn + queueing; greedy,
        # sampled, and top-k/top-p filtered rows
        reqs = [(list(range(3, 9)), 0.0, 0, 1.0),
                (list(range(3, 17)), 0.8, 0, 1.0),
                (list(range(5, 11)), 1.3, 5, 1.0),
                (list(range(2, 14)), 0.7, 0, 0.9),
                (list(range(9, 15)), 0.0, 0, 1.0)]
        for eng in (single, tp2):
            for p, t, k, tp_ in reqs:
                eng.submit(p, max_new_tokens=10, temperature=t,
                           top_k=k, top_p=tp_)
        drain(single, tp2)
        assert tokens(single) == tokens(tp2), (tokens(single), tokens(tp2))
        print("CHURN_PARITY_OK")

        # ---- evict-with-copy on SHARDED pools: byte-exact round trip ----
        mmu = MMU(MMUConfig(page_size=8, n_pages=8, host_pool_pages=64))
        eng = ServingEngine(cfg, params, mmu, max_batch=2, max_len=80,
                            seed=0, mesh=mesh)
        eng.submit(list(range(3, 30)), max_new_tokens=30)
        for _ in range(3):
            eng.step()
        se = mmu._seqs[1]
        pre = {p.vpage: eng._pager_gather(p.ppage)
               for p in se.pages if not p.on_host}
        mmu.alloc_seq(99, 8 * (len(mmu._free) + 2))   # pressure -> evict
        evicted = [p.vpage for p in se.pages if p.on_host]
        assert evicted
        for v in evicted:
            stored = mmu.host_page_data(1, v)
            np.testing.assert_array_equal(stored["k"], pre[v]["k"])
            np.testing.assert_array_equal(stored["v"], pre[v]["v"])
        mmu.free_seq(99)
        for v in evicted:                              # fault back in
            ppage, _ = mmu.translate(1, v * 8)
            flat = flat_page_indices([ppage], cfg.n_layers,
                                     mmu.config.n_pages)
            back = {k: np.asarray(x)
                    for k, x in gather_kv_pages(eng.pools, flat).items()}
            np.testing.assert_array_equal(back["k"], pre[v]["k"])
            np.testing.assert_array_equal(back["v"], pre[v]["v"])
        # pools stayed pinned to the TP layout through the scatter
        assert eng.pools["k"].sharding == eng.tp.kv_sharding
        print("TP2_SERVING_OK")
    """)
    _run_sub(script, "TP2_SERVING_OK")


@pytest.mark.slow
def test_tp4_token_parity_and_heads_degradation():
    """TP=4: with 4 kv heads the full stack shards; with the stock
    reduced config (2 kv heads) attention statically degrades to
    replication while the MLP still shards — parity must hold in BOTH
    regimes."""
    script = _PREAMBLE + textwrap.dedent("""
        mesh = mesh_or_skip(1, 4)
        base = get_config("smollm-135m").reduced()
        for cfg, want_heads in ((dataclasses.replace(base, n_kv_heads=4),
                                 True),
                                (base, False)):
            params = T.init_params(jax.random.PRNGKey(0), cfg,
                                   dtype=jnp.float32)

            def build(mesh):
                mmu = MMU(MMUConfig(page_size=16, n_pages=128))
                return ServingEngine(cfg, params, mmu, max_batch=3,
                                     max_len=64, seed=0, mesh=mesh)

            single, tp4 = build(None), build(mesh)
            assert tp4.tp.shard_heads is want_heads
            assert tp4.tp.shard_mlp is True
            for p, t in (([1, 2, 3, 4, 5], 0.0), ([7, 8, 9], 0.9),
                         (list(range(11, 18)), 1.2)):
                single.submit(p, max_new_tokens=8, temperature=t)
                tp4.submit(p, max_new_tokens=8, temperature=t)
            drain(single, tp4)
            assert tokens(single) == tokens(tp4), \\
                (want_heads, tokens(single), tokens(tp4))
        print("TP4_SERVING_OK")
    """)
    _run_sub(script, "TP4_SERVING_OK")


@pytest.mark.slow
def test_sharded_tenant_migrates_and_recovers():
    """PR-5 + PR-7 composition: a TP=2 tenant live-migrates to a
    SINGLE-DEVICE destination shell token-for-token (the wire format is
    shard-agnostic), and a TP=2 slot recovers in place KV-intact."""
    script = _PREAMBLE + textwrap.dedent("""
        from repro.core import Shell, ShellConfig, migrate
        mesh = mesh_or_skip(1, 2)
        cfg = get_config("smollm-135m").reduced()
        params = T.init_params(jax.random.PRNGKey(0), cfg,
                               dtype=jnp.float32)

        def shell():
            s = Shell(ShellConfig.make(
                services={"mmu": MMUConfig(page_size=16, n_pages=128)},
                n_vfpgas=2))
            s.build()
            return s

        def engine(sh, mesh):
            return ServingEngine(cfg, params, sh.services.get("mmu"),
                                 max_batch=3, max_len=128, shell=sh,
                                 slot=0, tenant="gold", mesh=mesh)

        reqs = [(list(range(3, 8)), 0.0), (list(range(3, 20)), 0.0),
                (list(range(3, 12)), 1.3)]

        def oracle():
            eng = ServingEngine(cfg, params,
                                MMU(MMUConfig(page_size=16, n_pages=128)),
                                max_batch=3, max_len=128)
            for p, t in reqs:
                eng.submit(p, max_new_tokens=12, temperature=t)
            return eng

        # ---- migrate: sharded source -> single-device destination ----
        src, dst = shell(), shell()
        eng_src, eng_dst = engine(src, mesh), engine(dst, None)
        want = oracle()
        for p, t in reqs:
            eng_src.submit(p, max_new_tokens=12, temperature=t)
        for _ in range(4):
            eng_src.step()
            want.step()
        report = migrate(src, dst, "gold")
        assert report.n_requests == 3
        drain(eng_dst, want)
        assert tokens(eng_dst) == tokens(want)
        assert src.services.get("mmu").utilization()["pages_used"] == 0
        src.close(); dst.close()
        print("MIGRATE_SHARDED_OK")

        # ---- recover_slot: sharded engine, in place, KV-intact ----
        sh = shell()
        eng = engine(sh, mesh)
        want = oracle()
        for p, t in reqs:
            eng.submit(p, max_new_tokens=12, temperature=t)
        for _ in range(4):
            eng.step()
            want.step()
        report = sh.recover_slot(0)
        assert report.n_requests == 3 and report.n_pages > 0
        # cold-reset preserved the TP layout
        assert eng.pools["k"].sharding == eng.tp.kv_sharding
        drain(eng, want)
        assert tokens(eng) == tokens(want)
        sh.close()
        print("RECOVER_SHARDED_OK")
    """)
    _run_sub(script, "RECOVER_SHARDED_OK")
