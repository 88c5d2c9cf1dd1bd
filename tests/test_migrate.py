"""Quiesce-and-migrate: live tenant migration across shells with real KV
copy, plus the evict-with-copy pager inside one shell."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (AppArtifact, Invocation, MigrationError, Oper,
                        PortState, SgEntry, Shell, ShellConfig, migrate)
from repro.core.bitstream import BitstreamError
from repro.core.migrate import decode_snapshot, encode_snapshot
from repro.core.port import PortError
from repro.core.services import MMUConfig
from repro.core.services.mmu import MMU
from repro.models import transformer as T
from repro.serve.engine import ServingEngine
from repro.serve.paged_model import flat_page_indices, gather_kv_pages

PAGE = 16
POOL = 128


@pytest.fixture(scope="module")
def served():
    cfg = get_config("smollm-135m").reduced()
    params = T.init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, params


def _shell(n_vfpgas=2):
    s = Shell(ShellConfig.make(
        services={"mmu": MMUConfig(page_size=PAGE, n_pages=POOL)},
        n_vfpgas=n_vfpgas))
    s.build()
    return s


def _engine(cfg, params, shell, *, tenant="gold", rid_base=0, slot=0):
    return ServingEngine(cfg, params, shell.services.get("mmu"),
                         max_batch=3, max_len=128, shell=shell, slot=slot,
                         tenant=tenant, rid_base=rid_base)


def _live_pages(engine):
    """{(rid, vpage): {"k": bytes, "v": bytes}} for device-resident pages."""
    out = {}
    mmu = engine.mmu
    for sid, se in mmu._seqs.items():
        for pte in se.pages:
            if pte.on_host:
                continue
            flat = flat_page_indices([pte.ppage], engine.cfg.n_layers,
                                     mmu.config.n_pages)
            kv = gather_kv_pages(engine.pools, flat)
            out[(sid, pte.vpage)] = {k: np.asarray(v)
                                     for k, v in kv.items()}
    return out


# ================================================== the migration story ====
def test_mid_decode_migrate_token_for_token_parity(served):
    """Acceptance pin: a live tenant migrated mid-decode produces exactly
    the tokens an unmigrated oracle produces — greedy AND sampled rows
    (the PRNG stream moves with the tenant)."""
    cfg, params = served
    src, dst = _shell(), _shell()
    eng_src = _engine(cfg, params, src)
    eng_dst = _engine(cfg, params, dst)
    oracle = ServingEngine(cfg, params, MMU(MMUConfig(page_size=PAGE,
                                                      n_pages=POOL)),
                           max_batch=3, max_len=128)
    reqs = [(list(range(3, 8)), 0.0), (list(range(3, 20)), 0.0),
            (list(range(3, 12)), 1.3)]
    for prompt, temp in reqs:
        eng_src.submit(prompt, max_new_tokens=12, temperature=temp)
        oracle.submit(prompt, max_new_tokens=12, temperature=temp)
    for _ in range(4):                       # mid-decode
        eng_src.step()
        oracle.step()
    report = migrate(src, dst, "gold")
    assert report.n_requests == 3
    assert report.downtime_s > 0
    while eng_dst.pending():
        eng_dst.step()
    while oracle.pending():
        oracle.step()
    got = {r.rid: r.out_tokens for r in eng_dst.completed}
    want = {r.rid: r.out_tokens for r in oracle.completed}
    assert got == want
    # the source tenant's pages are gone; the source engine is reusable
    assert src.services.get("mmu").utilization()["pages_used"] == 0
    assert eng_src.active == 0
    src.close()
    dst.close()


def test_migrate_kv_bytes_identical_post_restore(served):
    """Acceptance pin: every live KV page lands on the destination
    byte-identical, at its sequence's rebuilt mapping."""
    cfg, params = served
    src, dst = _shell(), _shell()
    eng_src = _engine(cfg, params, src)
    eng_dst = _engine(cfg, params, dst)
    for n in (5, 30, 17):
        eng_src.submit(list(range(3, 3 + n)), max_new_tokens=20)
    for _ in range(6):
        eng_src.step()
    before = _live_pages(eng_src)
    assert before                             # tenant has live KV
    # shared prefix pages (the 30- and 17-token prompts open with the
    # same first page) ship ONCE in the v2 wire format
    n_phys = len({pte.ppage
                  for se in eng_src.mmu._seqs.values()
                  for pte in se.pages if not pte.on_host})
    report = migrate(src, dst, 0)
    after = _live_pages(eng_dst)
    assert set(after) == set(before)
    for key in before:
        np.testing.assert_array_equal(before[key]["k"], after[key]["k"])
        np.testing.assert_array_equal(before[key]["v"], after[key]["v"])
    assert report.n_pages == n_phys <= len(before)
    assert report.payload_bytes > 0
    src.close()
    dst.close()


def test_migrate_replays_held_invocations_zero_lost_dup(served):
    """Invocations held while the source quiesces replay on the
    DESTINATION port: every future resolves exactly once, executed by
    the destination shell."""
    cfg, params = served
    src, dst = _shell(), _shell()
    _engine(cfg, params, src)
    _engine(cfg, params, dst)
    src_port, dst_port = src.attach(0), dst.attach(0)
    assert src_port.quiesce(timeout=10.0)     # idempotent under migrate()
    futs = [src_port.submit(Invocation.io(256, tenant="gold"))
            for _ in range(5)]
    assert src_port.held() == 5
    assert not futs[0].done()
    report = migrate(src, dst, "gold")
    assert report.replayed == 5
    for f in futs:
        comp = f.result(timeout=30.0)
        assert comp.ok
    # exactly-once: source held is empty, destination billed the replay
    assert src_port.held() == 0
    assert src_port.state is PortState.ACTIVE
    assert dst_port.stats()["replayed"] == 5
    dst.drain()
    assert dst.scheduler.stats()["tenants"]["gold"]["completions"] >= 5
    src.close()
    dst.close()


def test_bystander_tenants_on_both_shells_unaffected(served):
    """Bronze tenants drive slot-1 traffic on BOTH shells throughout the
    migration: everything completes, zero intake stalls."""
    cfg, params = served
    src, dst = _shell(), _shell()
    eng_src = _engine(cfg, params, src)
    _engine(cfg, params, dst)
    src.register_tenant("bronze_src", 1.0, slots=(1,))
    dst.register_tenant("bronze_dst", 1.0, slots=(1,))
    src.load_app(1, AppArtifact(name="echo", fn=lambda i, v, x: x))
    dst.load_app(1, AppArtifact(name="echo", fn=lambda i, v, x: x))
    p_src, p_dst = src.attach(1), dst.attach(1)
    eng_src.submit(list(range(3, 20)), max_new_tokens=24)
    for _ in range(3):
        eng_src.step()

    n = 80
    futs = {"src": [], "dst": []}

    def drive(port, key):
        for i in range(n):
            futs[key].append(port.submit(Invocation.from_sg(SgEntry(
                src=np.full(64, i % 251, np.uint8), length=64,
                opcode=Oper.LOCAL_TRANSFER))))

    threads = [threading.Thread(target=drive, args=(p_src, "src")),
               threading.Thread(target=drive, args=(p_dst, "dst"))]
    for t in threads:
        t.start()
    time.sleep(0.002)                        # bystanders in flight
    migrate(src, dst, "gold")
    for t in threads:
        t.join()
    for key in futs:
        comps = [f.result(timeout=30.0) for f in futs[key]]
        assert len(comps) == n and all(c.ok for c in comps)
    src.drain()
    dst.drain()
    for shell, tname in ((src, "bronze_src"), (dst, "bronze_dst")):
        stats = shell.scheduler.stats()["tenants"][tname]
        assert stats["completions"] == n
        assert stats["intake_stalls"] == 0
    src.close()
    dst.close()


def test_migrate_moves_queue_and_avoids_rid_collisions(served):
    """Queued (not yet admitted) requests ride the snapshot and complete
    on the destination; post-migration submissions on the destination
    never collide with adopted rids."""
    cfg, params = served
    src, dst = _shell(), _shell()
    eng_src = _engine(cfg, params, src)
    eng_dst = _engine(cfg, params, dst)
    for n in (5, 7, 9, 11, 6):               # 5 > max_batch=3: 2 queue
        eng_src.submit(list(range(3, 3 + n)), max_new_tokens=4)
    eng_src.step()                           # admit 3, leave 2 queued
    assert len(eng_src.queue) == 2
    report = migrate(src, dst, 0)
    assert report.n_queued == 2
    new_rid = eng_dst.submit(list(range(3, 9)), max_new_tokens=4)
    adopted = ([r.rid for r in eng_dst.slots if r is not None]
               + [r.rid for r in eng_dst.queue])
    assert new_rid not in adopted[:-1]
    while eng_dst.pending():
        eng_dst.step()
    assert len(eng_dst.completed) == 6       # 5 migrated + 1 new
    assert len({r.rid for r in eng_dst.completed}) == 6
    src.close()
    dst.close()


def test_migrate_capacity_refusal_leaves_source_serving(served):
    """An incoming tenant must FIT: restore never steals a resident
    tenant's pages, and the refused source keeps serving."""
    cfg, params = served
    src = _shell()
    dst = Shell(ShellConfig.make(
        services={"mmu": MMUConfig(page_size=PAGE, n_pages=2)},
        n_vfpgas=2))
    dst.build()
    eng_src = _engine(cfg, params, src)
    ServingEngine(cfg, params, dst.services.get("mmu"), max_batch=3,
                  max_len=128, shell=dst, slot=0, tenant="gold")
    eng_src.submit(list(range(3, 60)), max_new_tokens=8)   # 4 pages
    eng_src.step()
    with pytest.raises(MigrationError, match="free pages"):
        migrate(src, dst, "gold")
    assert src.attach(0).state is PortState.ACTIVE
    while eng_src.pending():
        eng_src.step()
    assert len(eng_src.completed) == 1
    src.close()
    dst.close()


def test_migrate_geometry_mismatch_leaves_source_serving(served):
    cfg, params = served
    src = _shell()
    dst = Shell(ShellConfig.make(
        services={"mmu": MMUConfig(page_size=PAGE * 2, n_pages=POOL)},
        n_vfpgas=2))
    dst.build()
    eng_src = _engine(cfg, params, src)
    _engine(cfg, params, dst)
    eng_src.submit(list(range(3, 12)), max_new_tokens=8)
    eng_src.step()
    with pytest.raises(MigrationError, match="geometry mismatch"):
        migrate(src, dst, "gold")
    # source untouched and still serving
    assert src.attach(0).state is PortState.ACTIVE
    while eng_src.pending():
        eng_src.step()
    assert len(eng_src.completed) == 1
    src.close()
    dst.close()


# ===================================================== snapshot format =====
def test_snapshot_version_and_corruption_rejected(served):
    cfg, params = served
    src = _shell()
    eng = _engine(cfg, params, src)
    eng.submit(list(range(3, 12)), max_new_tokens=6)
    eng.step()
    src.attach(0).quiesce(timeout=10.0)
    from repro.core.migrate import snapshot_tenant
    header, arrays = snapshot_tenant(src, 0)
    blob = encode_snapshot(header, arrays)
    # round-trip is fine
    h2, a2 = decode_snapshot(blob)
    assert h2["geometry"] == eng.geometry()
    # version-mismatched state container
    tampered = blob.replace(b'"state_version": 3', b'"state_version": 9', 1)
    with pytest.raises(BitstreamError, match="state version"):
        decode_snapshot(tampered)
    # wrong kind refuses before any state is touched
    wrong = blob.replace(b'"kind": "migration"', b'"kind": "app"', 1)
    with pytest.raises(BitstreamError):
        decode_snapshot(wrong)
    # bit-rot in the npz payload region
    import zipfile
    with pytest.raises((BitstreamError, zipfile.BadZipFile)):
        decode_snapshot(blob[: len(blob) // 2])
    # a pickle blob is refused outright
    import pickle
    with pytest.raises(BitstreamError, match="bad magic"):
        decode_snapshot(pickle.dumps({"kind": "migration"}))
    src.close()


# ==================================================== evict-with-copy ======
def test_evict_with_copy_restores_exact_kv_bytes(served):
    """Real KV migration on evict: the pager copies page payloads to the
    host store before the device page is recycled, and fault-back-in
    restores the exact bytes into the fresh page."""
    cfg, params = served
    mmu = MMU(MMUConfig(page_size=8, n_pages=8, host_pool_pages=64))
    eng = ServingEngine(cfg, params, mmu, max_batch=2, max_len=80)
    eng.submit(list(range(3, 30)), max_new_tokens=30)
    for _ in range(3):
        eng.step()
    se = mmu._seqs[1]
    pre = {p.vpage: eng._pager_gather(p.ppage)
           for p in se.pages if not p.on_host}
    mmu.alloc_seq(99, 8 * (len(mmu._free) + 2))   # pressure -> eviction
    evicted = [p.vpage for p in se.pages if p.on_host]
    assert evicted
    for v in evicted:
        stored = mmu.host_page_data(1, v)
        assert stored is not None
        np.testing.assert_array_equal(stored["k"], pre[v]["k"])
        np.testing.assert_array_equal(stored["v"], pre[v]["v"])
    assert mmu.migrations_out >= len(evicted)
    mmu.free_seq(99)                              # room to fault back in
    for v in evicted:
        ppage, _ = mmu.translate(1, v * 8)
        flat = flat_page_indices([ppage], cfg.n_layers, mmu.config.n_pages)
        back = {k: np.asarray(x)
                for k, x in gather_kv_pages(eng.pools, flat).items()}
        np.testing.assert_array_equal(back["k"], pre[v]["k"])
        np.testing.assert_array_equal(back["v"], pre[v]["v"])
        assert mmu.host_page_data(1, v) is None   # store drained
    assert mmu.migrations_in >= len(evicted)


def test_evicted_pages_ride_migration(served):
    """A tenant with host-evicted pages migrates whole: preserved
    payloads land device-resident on the destination, byte-exact."""
    cfg, params = served
    src = Shell(ShellConfig.make(
        services={"mmu": MMUConfig(page_size=8, n_pages=8,
                                   host_pool_pages=64)}, n_vfpgas=1))
    src.build()
    dst = Shell(ShellConfig.make(
        services={"mmu": MMUConfig(page_size=8, n_pages=32,
                                   host_pool_pages=64)}, n_vfpgas=1))
    dst.build()
    eng_src = ServingEngine(cfg, params, src.services.get("mmu"),
                            max_batch=2, max_len=80, shell=src, slot=0,
                            tenant="gold")
    eng_dst = ServingEngine(cfg, params, dst.services.get("mmu"),
                            max_batch=2, max_len=80, shell=dst, slot=0,
                            tenant="gold")
    eng_src.submit(list(range(3, 30)), max_new_tokens=30)
    for _ in range(3):
        eng_src.step()
    mmu = src.services.get("mmu")
    se = mmu._seqs[1]
    pre = {p.vpage: eng_src._pager_gather(p.ppage)
           for p in se.pages if not p.on_host}
    mmu.alloc_seq(99, 8 * (len(mmu._free) + 1))   # evict one page of seq 1
    evicted = [p.vpage for p in se.pages if p.on_host]
    assert evicted
    migrate(src, dst, "gold")
    dse = dst.services.get("mmu")._seqs[1]
    assert all(not p.on_host for p in dse.pages)  # fully device-resident
    for p in dse.pages:
        if p.vpage not in pre:
            continue
        flat = flat_page_indices([p.ppage], cfg.n_layers,
                                 dst.services.get("mmu").config.n_pages)
        got = {k: np.asarray(x)
               for k, x in gather_kv_pages(eng_dst.pools, flat).items()}
        np.testing.assert_array_equal(got["k"], pre[p.vpage]["k"])
        np.testing.assert_array_equal(got["v"], pre[p.vpage]["v"])
    src.close()
    dst.close()


# ============================================================ plumbing =====
def test_second_engine_on_shared_mmu_refused(served):
    """One paged-pool owner per MMU, enforced at construction: a second
    engine would gather/scatter evicted pages through the wrong pools."""
    cfg, params = served
    mmu = MMU(MMUConfig(page_size=PAGE, n_pages=POOL))
    eng = ServingEngine(cfg, params, mmu, max_batch=2, max_len=64)
    with pytest.raises(RuntimeError, match="pager"):
        ServingEngine(cfg, params, mmu, max_batch=2, max_len=64,
                      rid_base=1000)
    mmu.unregister_pager(eng)                 # owner may hand off
    ServingEngine(cfg, params, mmu, max_batch=2, max_len=64,
                  rid_base=1000)


def test_restore_held_replays_at_source_exactly_once(served):
    """The failed-replay fallback: invocations handed back via
    restore_held() rejoin the source's held FIFO and resolve exactly
    once on resume()."""
    cfg, params = served
    shell = _shell()
    _engine(cfg, params, shell)
    port = shell.attach(0)
    assert port.quiesce(timeout=10.0)
    futs = [port.submit(Invocation.io(128, tenant="gold"))
            for _ in range(4)]
    held = port.take_held()
    assert port.held() == 0
    port.restore_held(held)                   # the migration-abort path
    assert port.held() == 4
    replayed = port.resume()
    assert replayed == 4
    comps = [f.result(timeout=30.0) for f in futs]
    assert all(c.ok for c in comps)
    assert port.stats()["submitted"] == port.stats()["completed"] == 4
    shell.close()


def test_take_held_requires_quiesce(served):
    cfg, params = served
    shell = _shell()
    _engine(cfg, params, shell)
    port = shell.attach(0)
    with pytest.raises(PortError, match="quiesce"):
        port.take_held()
    shell.close()


def test_drain_tenant_is_tenant_scoped():
    shell = _shell()
    assert shell.scheduler.drain_tenant("nobody") is True
    shell.register_tenant("a", 1.0, slots=(0,))
    shell.load_app(0, AppArtifact(name="echo", fn=lambda i, v, x: x))
    port = shell.attach(0)
    futs = [port.submit(Invocation.from_sg(SgEntry(
        src=np.zeros(64, np.uint8), length=64,
        opcode=Oper.LOCAL_TRANSFER))) for _ in range(20)]
    assert shell.scheduler.drain_tenant("a", timeout=30.0)
    assert shell.scheduler.tenant_pending("a") == 0
    assert all(f.done() for f in futs)
    shell.close()
