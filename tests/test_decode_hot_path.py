"""Device-resident decode hot path: retrace guard, zero logits transfer,
Pallas-vs-ref engine parity across slot churn, incremental block tables,
drop-mode prefill scatter, fused sampling vs the host oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.services.mmu import MMU, MMUConfig
from repro.models import transformer as T
from repro.serve.engine import ServingEngine
from repro.serve.paged_model import (TRACE_COUNTS, decode_logits_paged,
                                     decode_step_paged, make_pools,
                                     write_prefill)
from repro.serve.sampler import sample_per_row


@pytest.fixture(scope="module")
def served():
    cfg = get_config("smollm-135m").reduced()
    params = T.init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, params


def _run_engine(cfg, params, *, use_pallas, prompts, new_tokens=4,
                max_batch=2, page=16):
    mmu = MMU(MMUConfig(page_size=page, n_pages=128))
    eng = ServingEngine(cfg, params, mmu, max_batch=max_batch, max_len=128,
                        use_pallas=use_pallas)
    for p in prompts:
        eng.submit(p, max_new_tokens=new_tokens)
    eng.run()
    return {r.rid: r.out_tokens for r in eng.completed}


# ------------------------------------------------------- retrace guard ----
def test_decode_compiles_exactly_once_across_occupancy_changes(served):
    cfg, params = served
    mmu = MMU(MMUConfig(page_size=16, n_pages=128))
    # max_len 144 -> max_pages 9: a (batch, table) shape unique to this
    # test, so the process-global jit cache cannot have compiled it yet
    # and the single-trace assertion is order-independent.
    eng = ServingEngine(cfg, params, mmu, max_batch=3, max_len=144)
    # wave 1: partial occupancy
    eng.submit(list(range(3, 10)), max_new_tokens=4)
    eng.submit(list(range(3, 20)), max_new_tokens=6)
    before = TRACE_COUNTS.get("decode_step_paged", 0)
    for _ in range(3):
        eng.step()
    # wave 2: occupancy changes mid-run (slots refill, lens cross pages)
    eng.submit(list(range(3, 36)), max_new_tokens=5)
    eng.submit(list(range(3, 8)), max_new_tokens=3)
    eng.run()
    assert len(eng.completed) == 4
    assert TRACE_COUNTS["decode_step_paged"] - before == 1, \
        "decode_step_paged must compile exactly once per engine shape"


def test_slot_update_compiles_once_and_tracks_host_truth(served):
    """The slot-state sync is one compiled program of fixed shape: over
    admissions, frees and page crossings that write 1, 2 and max_batch
    rows it traces once, and after every step the six device arrays hold
    the host truth for live rows and the cleared values for freed ones
    (a sampled, top-k and top-p row covers the float bit round trip)."""
    cfg, params = served
    B = 5        # a batch size unique to this test: order-independent
    mmu = MMU(MMUConfig(page_size=4, n_pages=128))
    eng = ServingEngine(cfg, params, mmu, max_batch=B, max_len=64, seed=2)
    sizes = []
    sync = eng._sync_slot_state

    def counting_sync(slots_i, rows):
        sizes.append(len(slots_i))
        sync(slots_i, rows)
    eng._sync_slot_state = counting_sync
    for n, new, temp, k, p in ((5, 2, 0.0, 0, 1.0), (7, 3, 0.0, 0, 1.0),
                               (9, 3, 0.7, 3, 0.8), (6, 8, 1.3, 0, 1.0),
                               (11, 8, 0.0, 0, 0.9)):
        eng.submit(list(range(3, 3 + n)), max_new_tokens=new,
                   temperature=temp, top_k=k, top_p=p)
    before = TRACE_COUNTS.get("slot_update", 0)

    def device_state():
        return {f: np.asarray(getattr(eng, "dev_" + f))
                for f in ("rids", "lens", "tokens", "temps", "topk", "topp")}
    held = [None] * B
    while eng.pending():
        eng.step()
        got = device_state()
        for i, req in enumerate(eng.slots):
            if req is not None:
                want = {"rids": req.rid,
                        "lens": len(req.prompt) + len(req.out_tokens) - 1,
                        "tokens": req.out_tokens[-1],
                        "temps": np.float32(req.temperature),
                        "topk": req.top_k, "topp": np.float32(req.top_p)}
            elif held[i] is not None:        # freed in this step
                want = {"rids": 0, "lens": 0, "tokens": 0,
                        "temps": np.float32(0.0), "topk": 0,
                        "topp": np.float32(1.0)}
            else:      # empty before: decode moves only lens and tokens
                want = {"rids": 0, "temps": np.float32(0.0), "topk": 0,
                        "topp": np.float32(1.0)}
            for f, v in want.items():
                assert got[f][i] == v, (eng.steps, i, f, got[f][i], v)
        held = list(eng.slots)
    assert len(eng.completed) == 5
    assert {1, 2, B} <= set(sizes), sizes
    assert eng.slot_syncs == len(sizes)
    assert eng.run()["slot_syncs"] == len(sizes)
    assert TRACE_COUNTS["slot_update"] - before == 1, \
        "update_slot_state must compile exactly once per engine shape"


def test_prefill_is_batched_one_forward_per_admit_wave(served):
    cfg, params = served
    mmu = MMU(MMUConfig(page_size=16, n_pages=128))
    # max_len 144 keeps this prefill bucket shape unique to this test
    # (see the retrace-guard note above)
    eng = ServingEngine(cfg, params, mmu, max_batch=4, max_len=144)
    for n in (5, 9, 12, 7):
        eng.submit(list(range(3, 3 + n)), max_new_tokens=2)
    before = TRACE_COUNTS.get("prefill_shared_paged", 0)
    eng.step()      # admits all 4 -> ONE batched prefill trace/call
    assert TRACE_COUNTS.get("prefill_shared_paged", 0) - before == 1
    assert all(len(r.out_tokens) >= 1 for r in eng.slots if r is not None)
    eng.run()
    assert len(eng.completed) == 4


def test_prompt_longer_than_max_len_completes_from_prefill(served):
    cfg, params = served
    mmu = MMU(MMUConfig(page_size=16, n_pages=128))
    eng = ServingEngine(cfg, params, mmu, max_batch=2, max_len=32)
    eng.submit(list(range(3, 3 + 40)), max_new_tokens=4)   # 40 > max_len
    eng.submit(list(range(3, 3 + 7)), max_new_tokens=3)
    stats = eng.run()
    assert stats["completed"] == 2
    long_req = next(r for r in eng.completed if len(r.prompt) == 40)
    assert len(long_req.out_tokens) == 1       # no decode budget left
    assert mmu.utilization()["pages_used"] == 0


# ----------------------------------------- only a (B,) vector crosses ----
def test_decode_step_outputs_no_logits(served):
    cfg, params = served
    b, maxp, n_pages, page = 4, 8, 64, 16
    pools = make_pools(cfg, n_pages, page)
    out = jax.eval_shape(
        lambda pr, po, t, l, lt, r, tp: decode_step_paged(
            pr, po, t, l, lt, r, tp, cfg=cfg, page_size=page),
        params, pools,
        jax.ShapeDtypeStruct((b, maxp), jnp.int32),
        jax.ShapeDtypeStruct((b,), jnp.int32),
        jax.ShapeDtypeStruct((b,), jnp.int32),
        jax.eval_shape(lambda: jax.random.PRNGKey(0)),
        jax.ShapeDtypeStruct((b,), jnp.float32))
    toks, new_pools, new_lens, _ = out
    assert toks.shape == (b,) and toks.dtype == jnp.int32
    assert new_lens.shape == (b,)
    # nothing vocab-shaped leaves the step: logits stay on device
    for leaf in jax.tree.leaves(out):
        assert cfg.vocab_size not in leaf.shape


# ------------------------------------- pallas == ref through the engine ----
def test_pallas_engine_matches_ref_engine_with_slot_churn(served):
    """Greedy decode through the Pallas kernel == jnp oracle, across
    continuous batching with slots freed and refilled mid-run and lens
    crossing page boundaries."""
    cfg, params = served
    # 5 requests through 2 slots -> churn; prompt 16 lands exactly on a
    # page boundary (page_size=16)
    prompts = [list(range(3, 3 + n)) for n in (16, 5, 12, 9, 17)]
    ref = _run_engine(cfg, params, use_pallas=False, prompts=prompts)
    pal = _run_engine(cfg, params, use_pallas=True, prompts=prompts)
    assert ref == pal


def test_decode_logits_kernel_matches_ref_and_step_appends_in_place(served):
    """``decode_logits_paged``: the Pallas kernel's logits match the XLA
    reference on one scattered pool state, each live row's KV lands at
    (its page, its offset) of the head-major pool and nowhere else, an
    inactive row writes nothing, and the sampling step's greedy tokens
    and pools agree with the logits program."""
    cfg, params = served
    page, n_pages = 16, 32
    shape = make_pools(cfg, n_pages, page)["k"].shape
    keys = jax.random.split(jax.random.PRNGKey(2))
    pools = {s: jax.random.normal(k, shape) for s, k in zip("kv", keys)}
    tables = jnp.asarray([[5, 9, -1, -1], [2, -1, -1, -1],
                          [-1, -1, -1, -1]], jnp.int32)
    lens = jnp.asarray([20, 15, 0], jnp.int32)    # row 2 is an empty slot
    last = jnp.asarray([7, 8, 0], jnp.int32)
    out = {p: decode_logits_paged(
               params, pools, tables, lens, last, cfg=cfg, page_size=page,
               use_pallas=p) for p in (True, False)}
    lp, lr = (np.asarray(out[p][0]) for p in (True, False))
    assert lp.shape == (3, cfg.vocab_size) and np.isfinite(lp).all()
    np.testing.assert_allclose(lp, lr, rtol=0,
                               atol=1e-5 * np.abs(lr).max())
    for s in "kv":
        # layer 0's attention rounds differently per path, so deeper
        # layers append slightly different KV
        np.testing.assert_allclose(out[True][1][s], out[False][1][s],
                                   rtol=0, atol=1e-5)
        new = np.asarray(out[False][1][s])
        old = np.asarray(pools[s])
        written = np.zeros(shape[:1] + shape[2:3], bool)   # (slot, offset)
        for layer in range(cfg.n_layers):
            written[layer * n_pages + 9, 20 % page] = True
            written[layer * n_pages + 2, 15] = True
        changed = (new != old).any(axis=(1, 3))
        np.testing.assert_array_equal(changed, written)

    toks, step_pools, _, _ = decode_step_paged(
        params, jax.tree.map(jnp.copy, pools), tables, lens, last,
        jax.random.PRNGKey(0), jnp.zeros((3,), jnp.float32), cfg=cfg,
        page_size=page, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(toks)[:2], lr[:2].argmax(-1))
    for s in "kv":
        np.testing.assert_allclose(step_pools[s], out[False][1][s],
                                   rtol=0, atol=1e-5)


# ----------------------------------------------- incremental tables ----
def test_device_block_table_is_incremental():
    mmu = MMU(MMUConfig(page_size=4, n_pages=64))
    bt = mmu.block_table_device(n_slots=2, max_pages=8)
    mmu.alloc_seq(1, 6)                      # 2 pages
    bt.bind(0, 1)
    t0 = np.asarray(bt.device_view())
    np.testing.assert_array_equal(t0[0], mmu.block_table([1], 8)[0])
    assert t0[1][0] == -1
    up0 = bt.row_uploads
    # steady state within a page: repeated views are pure cache hits
    mmu.extend_seq(1, 1)                     # 7 tokens, still 2 pages
    for _ in range(3):
        bt.device_view()
    assert bt.row_uploads == up0
    assert bt.hits >= 3
    # page-boundary crossing dirties exactly one row
    mmu.extend_seq(1, 2)                     # 9 tokens -> 3rd page
    t1 = np.asarray(bt.device_view())
    assert bt.row_uploads == up0 + 1
    np.testing.assert_array_equal(t1[0], mmu.block_table([1], 8)[0])
    # free + unbind clears the row
    mmu.free_seq(1)
    bt.unbind(0)
    t2 = np.asarray(bt.device_view())
    assert (t2[0] == -1).all()


def test_device_block_table_tracks_eviction():
    mmu = MMU(MMUConfig(page_size=4, n_pages=4, host_pool_pages=16))
    bt = mmu.block_table_device(n_slots=2, max_pages=8)
    mmu.alloc_seq(1, 12)                     # 3 of 4 pages
    bt.bind(0, 1)
    bt.device_view()
    mmu.alloc_seq(2, 8)                      # forces eviction of seq 1 tail
    bt.bind(1, 2)
    t = np.asarray(bt.device_view())
    host = mmu.block_table([1, 2], 8)
    np.testing.assert_array_equal(t, host)
    assert (t[0] == -1).sum() >= 6           # evicted tail page shows as -1


# ------------------------------------------------ drop-mode scatter ----
def test_write_prefill_drops_invalid_writes(served):
    cfg, _ = served
    n_pages, page, b, s = 8, 4, 2, 10
    hd = cfg.resolved_head_dim
    kh = cfg.n_kv_heads
    L = cfg.n_layers
    sentinel = 7.5
    pools = {k: jnp.full((L * n_pages, kh, page, hd), sentinel)
             for k in ("k", "v")}
    ks = jax.random.normal(jax.random.PRNGKey(0), (L, b, s, kh, hd))
    vs = ks + 1.0
    tables = jnp.asarray([[2, 5, 1, -1], [6, -1, -1, -1]], jnp.int32)
    lens = jnp.asarray([10, 3], jnp.int32)
    out = write_prefill(pools, (ks, vs), tables, lens, page)
    # flat head-major layout: layer l's page p lives at slot l*n_pages + p;
    # viewed token-major below so positions index axis 2
    outk = np.asarray(out["k"]).reshape(L, n_pages, kh, page, hd)
    outk = outk.swapaxes(2, 3)
    # mapped positions hold the prefill KV
    np.testing.assert_allclose(outk[:, 2], np.asarray(ks[:, 0, 0:4]))
    np.testing.assert_allclose(outk[:, 5], np.asarray(ks[:, 0, 4:8]))
    np.testing.assert_allclose(outk[:, 6, :3], np.asarray(ks[:, 1, 0:3]))
    # row 0 page 1 (vpage 2) holds tokens 8..9 only; offsets 2..3 untouched
    np.testing.assert_allclose(outk[:, 1, :2], np.asarray(ks[:, 0, 8:10]))
    assert (outk[:, 1, 2:] == sentinel).all()
    # rows' padding (beyond lens) and unmapped pages never get written:
    # every untouched pool page still holds the sentinel
    for pg in (0, 3, 4, 7):
        assert (outk[:, pg] == sentinel).all(), f"page {pg} was clobbered"
    assert (outk[:, 6, 3:] == sentinel).all()


# ------------------------------------------------------ fused sampler ----
def test_sample_per_row_matches_host_oracle():
    rng = jax.random.PRNGKey(0)
    logits = jax.random.normal(jax.random.PRNGKey(1), (6, 33)) * 3.0
    # greedy rows == argmax; near-zero temperature converges to argmax
    temps = jnp.asarray([0.0, -1.0, 1e-4, 1e-4, 0.0, 1e-4])
    toks = np.asarray(sample_per_row(rng, logits, temps))
    np.testing.assert_array_equal(
        toks, np.argmax(np.asarray(logits), axis=-1))
    # hot rows: valid token range, and temperature actually randomizes
    temps = jnp.full((6,), 2.0)
    draws = {tuple(np.asarray(sample_per_row(jax.random.PRNGKey(s),
                                             logits, temps)))
             for s in range(8)}
    assert len(draws) > 1
    for d in draws:
        assert all(0 <= t < 33 for t in d)


def test_engine_temperature_uses_device_sampler(served):
    cfg, params = served
    mmu = MMU(MMUConfig(page_size=16, n_pages=64))
    eng = ServingEngine(cfg, params, mmu, max_batch=1, max_len=64, seed=3)
    eng.submit(list(range(3, 12)), max_new_tokens=8, temperature=1.5)
    eng.run()
    sampled = eng.completed[0].out_tokens
    assert all(0 <= t < cfg.vocab_size for t in sampled)
    # host oracle is exposed for cross-checks and stays vectorized
    fake = np.zeros((4, cfg.vocab_size), np.float32)
    fake[:, 5] = 100.0
    np.testing.assert_array_equal(eng._sample(fake, 0.0), [5, 5, 5, 5])
    assert eng._sample(fake, 1.0).shape == (4,)


def test_engine_per_request_topk1_matches_greedy_stream(served):
    """Per-request sampler filters: a top_k=1 request at high temperature
    is deterministic and must emit exactly the greedy token stream, while
    sharing the batch with a plain greedy request (no cross-row leak)."""
    cfg, params = served
    prompts = [list(range(3, 12)), list(range(4, 13))]

    def run(**kw):
        mmu = MMU(MMUConfig(page_size=16, n_pages=128))
        eng = ServingEngine(cfg, params, mmu, max_batch=2, max_len=96)
        eng.submit(prompts[0], max_new_tokens=6, **kw)
        eng.submit(prompts[1], max_new_tokens=6)
        eng.run()
        return {tuple(r.prompt): r.out_tokens for r in eng.completed}

    greedy = run()
    hot_k1 = run(temperature=5.0, top_k=1)
    assert hot_k1[tuple(prompts[0])] == greedy[tuple(prompts[0])]
    assert hot_k1[tuple(prompts[1])] == greedy[tuple(prompts[1])]


def test_engine_per_request_filters_keep_single_trace(served):
    """Adding per-request top-k/top-p must not break the retrace guard:
    decode still compiles once per engine shape across filter churn."""
    cfg, params = served
    mmu = MMU(MMUConfig(page_size=16, n_pages=128))
    # max_len 160 -> a table shape unique to this test
    eng = ServingEngine(cfg, params, mmu, max_batch=2, max_len=160)
    eng.submit(list(range(3, 10)), max_new_tokens=3)
    before = TRACE_COUNTS.get("decode_step_paged", 0)
    eng.step()
    eng.submit(list(range(3, 14)), max_new_tokens=3,
               temperature=2.0, top_k=4, top_p=0.8)   # filters switch ON
    eng.run()
    assert TRACE_COUNTS["decode_step_paged"] - before == 1
    assert len(eng.completed) == 2


def test_decode_pages_span_counts_kernel_pages(served, tmp_path):
    """Each decode step records, before its dispatch, the pages the paged
    kernel reads per layer (each decoding row's context with the token
    being appended, in pages) and the block table's width over all rows."""
    cfg, params = served
    page, max_batch, max_len = 16, 3, 128
    mmu = MMU(MMUConfig(page_size=page, n_pages=128))
    eng = ServingEngine(cfg, params, mmu, max_batch=max_batch,
                        max_len=max_len)
    prompts = [list(range(3, 3 + n)) for n in (15, 40)]
    for p in prompts:
        eng.submit(p, max_new_tokens=5)
    eng.step()                                # prefill, first decode
    with jax.profiler.trace(str(tmp_path)):
        eng.run()
    (xplane,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    events = [e for plane in jax.profiler.ProfileData.from_file(
        str(xplane)).planes for line in plane.lines for e in line.events]
    pages = [dict(e.stats) for e in events
             if e.name == "repro.engine.decode_pages"]
    dispatches = [e for e in events
                  if e.name == "repro.engine.decode_dispatch"]
    assert len(pages) == len(dispatches) == 3
    # from the second decode on: 2, 3, 4 tokens past each prompt
    assert [p["live_pages"] for p in pages] == [
        sum(-(-(len(q) + k) // page) for q in prompts) for k in (2, 3, 4)]
    assert {p["table_pages"] for p in pages} == {
        max_batch * -(-max_len // page)}
