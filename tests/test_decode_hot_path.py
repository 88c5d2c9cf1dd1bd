"""Device-resident decode hot path: retrace guard, zero logits transfer,
Pallas-vs-ref engine parity across slot churn, incremental block tables,
the prefill write mask, one layer body behind prefill, chunked prefill
and decode, fused sampling vs the host oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.services.mmu import MMU, MMUConfig
from repro.models import attention, layers
from repro.models import transformer as T
from repro.serve.engine import ServingEngine
from repro.serve.paged_model import (TRACE_COUNTS, decode_logits_paged,
                                     decode_step_paged, make_pools,
                                     prefill_chunk_paged,
                                     prefill_shared_paged)
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


def _one_seq_table():
    """A 4-page MMU with seq 1 (6 tokens, 2 pages) bound to slot 0 of a
    2-slot table, after its first view."""
    mmu = MMU(MMUConfig(page_size=4, n_pages=4, host_pool_pages=16))
    bt = mmu.block_table_device(n_slots=2, max_pages=8)
    mmu.alloc_seq(1, 6)
    bt.bind(0, 1)
    bt.device_view()
    return mmu, bt


def test_device_block_table_hits_transfer_nothing():
    mmu, bt = _one_seq_table()
    t0, up0, hits0 = bt.device_view(), bt.uploads, bt.hits
    mmu.extend_seq(1, 1)                     # 7 tokens, still 2 pages
    for _ in range(3):
        assert bt.device_view() is t0        # the cached array, no transfer
    assert bt.uploads == up0
    assert bt.hits == hits0 + 3
    assert bt.last_updated_rows == []


def _page_crossing(mmu, bt):
    mmu.extend_seq(1, 3)                     # 9 tokens -> a 3rd page
    return [1]


def _bind(mmu, bt):
    mmu.alloc_seq(2, 5)
    bt.bind(1, 2)
    return [1, 2]


def _unbind(mmu, bt):
    mmu.free_seq(1)
    bt.unbind(0)
    return [-1]


def _eviction(mmu, bt):
    mmu.alloc_seq(2, 12)                     # 3 pages of 4: evicts seq 1's
    return [1]


@pytest.mark.parametrize("event", [_page_crossing, _bind, _unbind,
                                   _eviction],
                         ids=["page_crossing", "bind", "unbind", "eviction"])
def test_device_block_table_uploads_once_per_change(event):
    """Each kind of change leaves the device view equal to the MMU's own
    block table, for one transfer of the whole mirror."""
    mmu, bt = _one_seq_table()
    before, up0 = np.asarray(bt.device_view()).copy(), bt.uploads
    seqs = event(mmu, bt)
    t = np.asarray(bt.device_view())
    assert bt.uploads == up0 + 1
    assert not np.array_equal(t, before)
    want = np.full((2, 8), -1, np.int32)
    for i, sid in enumerate(seqs):
        if sid >= 0:
            want[i] = mmu.block_table([sid], 8)[0]
    np.testing.assert_array_equal(t, want)
    bt.device_view()                         # and nothing more after it
    assert bt.uploads == up0 + 1


def test_device_block_table_view_does_not_alias_the_host_mirror():
    """A view already handed out keeps its values when the host mirror is
    written in place (unbind, the refresh of a changed row).  The mirror
    sits at the 64-byte alignment at which the CPU backend would take a
    numpy buffer without copying it."""
    mmu = MMU(MMUConfig(page_size=4, n_pages=64))
    bt = mmu.block_table_device(n_slots=2, max_pages=8)
    raw = np.empty(bt._host.nbytes + 64, np.uint8)
    off = -raw.ctypes.data % 64
    bt._host = raw[off:off + bt._host.nbytes].view(np.int32).reshape(2, 8)
    bt._host[:] = -1
    mmu.alloc_seq(1, 6)
    mmu.alloc_seq(2, 9)
    bt.bind(0, 1)
    bt.bind(1, 2)
    held = bt.device_view()
    want = np.asarray(held).copy()
    bt.unbind(0)
    mmu.extend_seq(2, 4)                     # row 1 crosses into a 4th page
    t = np.asarray(bt.device_view())
    assert (t[0] == -1).all()
    np.testing.assert_array_equal(t[1], mmu.block_table([2], 8)[0])
    np.testing.assert_array_equal(np.asarray(held), want)


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


# ------------------------------------------------ prefill write mask ----
def _slot_offsets(table, positions, n_layers, n_pages, page):
    """(flat slot, in-page offset) of each position's KV in every layer;
    positions on an unmapped (-1) page are left out."""
    return {(layer * n_pages + table[q // page], q % page)
            for layer in range(n_layers) for q in positions
            if table[q // page] >= 0}


def test_prefill_write_mask_drops_covered_padded_unmapped_and_earlier(
        served):
    """The serving prefill writes KV only where the write mask allows: not
    at a covered prefix below ``write_from``, not past a row's ``q_len``
    (padding), not through an unmapped (-1) table entry, and — for a
    later chunk — never below its ``q_start``.  Pools start as a sentinel;
    after a two-row ``prefill_shared_paged`` and a ``prefill_chunk_paged``
    every other (slot, offset) still holds it in every layer, and every
    written one holds KV."""
    cfg, params = served
    L, page, n_pages, sentinel = cfg.n_layers, 4, 16, 7.5
    shape = make_pools(cfg, n_pages, page)["k"].shape
    pools = {s: jnp.full(shape, sentinel, jnp.float32) for s in "kv"}
    tok = np.random.default_rng(0).integers(3, cfg.vocab_size, (3, 12))
    # row 0: positions 2..9 run, 2..3 covered (write_from 4), 10.. padding
    #        (page 1's offsets 2..3 mapped, vpage 3 unmapped);
    # row 1: positions 0..9 run, vpage 1 unmapped, 10..11 padding on page 3
    tables = np.asarray([[2, 5, 1, -1], [6, -1, 3, -1]], np.int32)
    q_lens, q_starts, write_from = [8, 10], [2, 0], [4, 0]
    _, pools, _ = prefill_shared_paged(
        params, pools, jnp.asarray(tok[:2], jnp.int32),
        jnp.asarray(q_lens, jnp.int32), jnp.asarray(q_starts, jnp.int32),
        jnp.asarray(write_from, jnp.int32), jnp.asarray(tables),
        jax.random.PRNGKey(0), jnp.zeros((2,), jnp.float32), cfg=cfg,
        page_size=page)
    written = (_slot_offsets(tables[0], range(4, 10), L, n_pages, page)
               | _slot_offsets(tables[1], range(0, 10), L, n_pages, page))
    after_shared = {s: np.asarray(pools[s]) for s in "kv"}
    # a later chunk of a third row: positions 4..9 run, page 7 (0..3) is
    # an earlier chunk's, vpage 2 unmapped, 10..15 padding (12.. on page 9)
    chunk_table = np.asarray([[7, 8, -1, 9]], np.int32)
    pools = prefill_chunk_paged(
        params, pools, jnp.asarray(tok[2:], jnp.int32),
        jnp.asarray([6], jnp.int32), jnp.asarray([4], jnp.int32),
        jnp.asarray(chunk_table), cfg=cfg, page_size=page)
    chunk_written = _slot_offsets(chunk_table[0], range(4, 10), L, n_pages,
                                  page)
    assert len(written) == L * 12 and len(chunk_written) == L * 4
    for s in "kv":
        got = np.asarray(pools[s])
        for slot in range(L * n_pages):
            for off in range(page):
                cell = got[slot, :, off]
                if (slot, off) in chunk_written:
                    assert (cell != sentinel).all(), (s, slot, off)
                elif (slot, off) in written:
                    assert (cell != sentinel).all(), (s, slot, off)
                    # the chunk leaves what it does not write as it was
                    np.testing.assert_array_equal(
                        cell, after_shared[s][slot, :, off])
                else:
                    assert (cell == sentinel).all(), (s, slot, off)
    # layer 0's KV depends on its own token only: row 1's lands where
    # its positions map, from the token at that position
    lp0 = jax.tree.map(lambda a: a[0], params["layers"])
    h = layers.norm_apply(lp0["norm1"], layers.embed_lookup(
        params["embed"], jnp.asarray(tok[1:2, :10])), cfg.norm_eps)
    _, k0, v0 = attention.qkv_proj(lp0["attn"], cfg, h)
    k0 = layers.apply_rope(k0, jnp.arange(10)[None], cfg.rope_theta)
    for s, want in (("k", k0[0]), ("v", v0[0])):
        for q in (0, 1, 2, 3, 8, 9):
            np.testing.assert_allclose(
                np.asarray(pools[s])[tables[1][q // page], :, q % page],
                want[q], rtol=1e-5, atol=1e-5)


# ------------------------------------------- one body, three programs ----
def _prompt_kv(pools, table, n, n_layers, n_pages, page):
    """(L, n, K, hd) per side: the KV held at prompt positions 0..n-1."""
    slots = np.asarray([[layer * n_pages + table[q // page]
                         for q in range(n)] for layer in range(n_layers)])
    offs = np.arange(n) % page
    return {s: np.asarray(pools[s])[slots, :, offs] for s in "kv"}


@pytest.mark.parametrize("chunks", [(8,), (4, 4), (5, 6)],
                         ids=["one_chunk", "two_chunks", "unaligned"])
def test_prefill_chunk_and_decode_share_the_layer_math(served, chunks):
    """One prompt's KV reached three ways — (a) one ``prefill_shared_paged``
    over the whole prompt, (b) ``prefill_chunk_paged`` chunks then a final
    ``prefill_shared_paged``, (c) ``prefill_shared_paged`` over all but
    the last token then ``decode_logits_paged`` on it — is the same at
    every prompt position; (a)'s and (b)'s greedy first token is the
    argmax of (c)'s logits; and the next decode step's logits agree from
    all three pool states."""
    cfg, params = served
    page, n_pages, n = 4, 16, 15
    table = np.asarray([[9, 2, 14, 5, 11]], np.int32)
    prompt = np.random.default_rng(1).integers(3, cfg.vocab_size, n)
    kw = dict(cfg=cfg, page_size=page)

    def vec(*v, dtype=jnp.int32):
        return jnp.asarray(v, dtype)

    def shared(pools, start, stop):
        return prefill_shared_paged(
            params, pools, vec(*prompt[start:stop])[None], vec(stop - start),
            vec(start), vec(start), jnp.asarray(table),
            jax.random.PRNGKey(0), vec(0.0, dtype=jnp.float32), **kw)

    first_a, pools_a, _ = shared(make_pools(cfg, n_pages, page), 0, n)
    pools_b, start = make_pools(cfg, n_pages, page), 0
    for size in chunks:
        pools_b = prefill_chunk_paged(
            params, pools_b, vec(*prompt[start:start + size])[None],
            vec(size), vec(start), jnp.asarray(table), **kw)
        start += size
    first_b, pools_b, _ = shared(pools_b, start, n)
    _, pools_c, _ = shared(make_pools(cfg, n_pages, page), 0, n - 1)
    logits_c, pools_c = decode_logits_paged(
        params, pools_c, jnp.asarray(table), vec(n - 1), vec(prompt[-1]),
        **kw)

    assert int(first_a[0]) == int(first_b[0]) == int(
        np.argmax(np.asarray(logits_c)[0]))
    kv_a = _prompt_kv(pools_a, table[0], n, cfg.n_layers, n_pages, page)
    for pools in (pools_b, pools_c):
        kv = _prompt_kv(pools, table[0], n, cfg.n_layers, n_pages, page)
        for s in "kv":
            np.testing.assert_allclose(kv[s], kv_a[s], rtol=0, atol=1e-5)
    nxt = [np.asarray(decode_logits_paged(
        params, pools, jnp.asarray(table), vec(n), vec(int(first_a[0])),
        **kw)[0]) for pools in (pools_a, pools_b, pools_c)]
    for logits in nxt[1:]:
        np.testing.assert_allclose(logits, nxt[0], rtol=0,
                                   atol=1e-5 * np.abs(nxt[0]).max())


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
