"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes/dtypes, plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # offline env: deterministic shim
    from _hypothesis_fallback import given, settings, strategies as st

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.paged_attention.paged_attention import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.kernels.ssd.ref import ssd_chunked, ssd_sequential
from repro.kernels.ssd.ssd import ssd_chunked_pallas


# ============================================================ flash attn ===
FA_CASES = [
    # b, h, kh, sq, sk, d, causal, window, dtype
    (2, 4, 2, 256, 256, 64, True, 0, jnp.float32),
    (1, 8, 8, 128, 384, 128, True, 0, jnp.float32),
    (2, 4, 1, 200, 200, 64, True, 0, jnp.float32),    # pad path
    (1, 4, 2, 256, 256, 64, True, 128, jnp.float32),  # SWA
    (1, 2, 2, 128, 256, 64, False, 0, jnp.float32),   # cross-attn
    (1, 4, 2, 128, 128, 64, True, 0, jnp.bfloat16),   # low precision
]


@pytest.mark.parametrize("case", FA_CASES,
                         ids=[f"fa{i}" for i in range(len(FA_CASES))])
def test_flash_attention_matches_ref(case):
    b, h, kh, sq, sk, d, causal, window, dtype = case
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, h, sq, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, kh, sk, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, kh, sk, d), jnp.float32).astype(dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    atol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


@settings(max_examples=10, deadline=None)
@given(sq=st.integers(17, 192), sk=st.integers(17, 192),
       blk=st.sampled_from([32, 64, 128]))
def test_flash_attention_block_size_invariance(sq, sk, blk):
    """Property: output is independent of block tiling."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 2, sq, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, sk, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, sk, 64), jnp.float32)
    a = flash_attention(q, k, v, block_q=blk, block_k=blk, interpret=True)
    b = attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


# ============================================================ paged attn ===
PA_CASES = [
    (2, 8, 2, 64, 128, 4, 16),
    (3, 4, 4, 128, 64, 6, 32),
    (1, 16, 8, 64, 256, 3, 8),
]


def _tables(b, page, maxp, npages, lens):
    tables = np.full((b, maxp), -1, np.int32)
    for i in range(b):
        need = -(-int(lens[i]) // page)
        tables[i, :need] = np.random.RandomState(i).permutation(
            npages)[:need]
    return tables


@pytest.mark.parametrize("ppb", [1, 2, None],
                         ids=["ppb1", "ppb2", "ppbauto"])
@pytest.mark.parametrize("case", PA_CASES,
                         ids=[f"pa{i}" for i in range(len(PA_CASES))])
def test_paged_attention_matches_ref(case, ppb):
    b, h, kh, d, page, maxp, npages = case
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
    kp = jax.random.normal(ks[1], (npages, kh, page, d), jnp.float32)
    vp = jax.random.normal(ks[2], (npages, kh, page, d), jnp.float32)
    lens = np.minimum(np.arange(1, b + 1) * (page + 7), page * maxp)
    tables = _tables(b, page, maxp, npages, lens)
    out = paged_attention(q, kp, vp, jnp.asarray(tables),
                          jnp.asarray(lens, jnp.int32),
                          pages_per_block=ppb, interpret=True)
    ref = paged_attention_ref(q, kp, vp, jnp.asarray(tables),
                              jnp.asarray(lens, jnp.int32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_paged_attention_ragged_occupancy_page_groups():
    """pages_per_block > 1 over ragged occupancy: an empty slot (all -1),
    a length exactly on a page-group boundary, and a host-swapped page
    (-1 mid-table) all match the oracle for every group width."""
    b, h, kh, d, page, maxp, npages = 3, 4, 2, 64, 16, 7, 32
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
    kp = jax.random.normal(ks[1], (npages, kh, page, d), jnp.float32)
    vp = jax.random.normal(ks[2], (npages, kh, page, d), jnp.float32)
    lens = jnp.asarray([0, 32, 100], jnp.int32)
    tables = np.full((b, maxp), -1, np.int32)
    tables[1, :2] = [5, 9]
    tables[2, :7] = [1, 2, 3, -1, 4, 6, 7]
    ref = paged_attention_ref(q, kp, vp, jnp.asarray(tables), lens)
    for ppb in (1, 2, 3, 4, None):        # 3: maxp not a group multiple
        out = paged_attention(q, kp, vp, jnp.asarray(tables), lens,
                              pages_per_block=ppb, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, err_msg=f"ppb={ppb}")


def _case(kh=2, group=2, page=16, maxp=8, ppb=2, lens=(40, 77, 128, 9),
          holes=()):
    return dict(kh=kh, group=group, page=page, maxp=maxp, ppb=ppb,
                lens=lens, holes=holes)


# one grid step holds every kv head of a row: head and group counts of
# phi3 (10 kv / 4), smollm (3 / 3), a TP=2 shard of phi3 (5) and MQA (1)
PA_SHAPE_CASES = {
    f"kv{kh}_g{g}": _case(kh=kh, group=g)
    for kh in (1, 3, 5, 10) for g in (1, 3, 4)}
PA_SHAPE_CASES.update({
    "len_mid_page": _case(lens=(1, 17, 45, 100)),
    "len_group_boundary": _case(lens=(32, 64, 96, 128)),
    "len_full_width": _case(lens=(128, 128, 127, 113)),
    "empty_rows": _case(lens=(0, 50, 0, 0)),
    "hole_in_live_range": _case(lens=(100, 128, 60, 33),
                                holes=((0, 2), (1, 0), (1, 7), (2, 3))),
    "width_not_ppb_multiple": _case(maxp=7, ppb=3, lens=(112, 111, 49, 0)),
    "ppb_over_width": _case(maxp=3, ppb=4, lens=(48, 20, 33, 1)),
})


def _pa_inputs(kh, group, page, maxp, lens, holes, d=64, npages=48,
               seed=11):
    b = len(lens)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, kh * group, d), jnp.float32)
    kp = jax.random.normal(ks[1], (npages, kh, page, d), jnp.float32)
    vp = jax.random.normal(ks[2], (npages, kh, page, d), jnp.float32)
    lens = np.asarray(lens, np.int32)
    tables = _tables(b, page, maxp, npages, lens)
    for i, j in holes:                     # host-swapped page: unmapped
        tables[i, j] = -1
    return q, kp, vp, tables, lens


@pytest.mark.parametrize("name", sorted(PA_SHAPE_CASES))
def test_paged_attention_heads_and_lengths(name):
    """Every kv head of a row in one grid step, over head/group counts,
    lengths mid-page, on a group boundary and at the full table width,
    empty rows, -1 holes inside the live range, and a table width that
    is not a multiple of the page group: the oracle's output."""
    c = PA_SHAPE_CASES[name]
    q, kp, vp, tables, lens = _pa_inputs(c["kh"], c["group"], c["page"],
                                         c["maxp"], c["lens"], c["holes"])
    args = (q, kp, vp, jnp.asarray(tables), jnp.asarray(lens))
    out = paged_attention(*args, pages_per_block=c["ppb"], interpret=True)
    ref = paged_attention_ref(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("ppb", [1, 2, 3, 4])
def test_paged_attention_never_reads_dead_pages(ppb):
    """Table entries past each row's last live page (in its last live
    group and in every group after it) map real pages full of NaN: the
    kernel never reads them, so the output is the oracle's with those
    entries unmapped (-1)."""
    kh, group, page, maxp, npages = 2, 3, 16, 9, 40
    lens = (1, 16, 17, 50, 0, 144)
    q, kp, vp, _, lens = _pa_inputs(kh, group, page, maxp, lens, (),
                                    npages=npages)
    nan_pages = np.arange(npages - 8, npages)
    kp = kp.at[nan_pages].set(jnp.nan)
    vp = vp.at[nan_pages].set(jnp.nan)
    tables = _tables(len(lens), page, maxp, nan_pages[0], lens)
    poisoned = tables.copy()
    for i, n in enumerate(-(-lens // page)):
        dead = np.arange(n, maxp)
        poisoned[i, dead] = nan_pages[dead % len(nan_pages)]
    out = paged_attention(q, kp, vp, jnp.asarray(poisoned),
                          jnp.asarray(lens), pages_per_block=ppb,
                          interpret=True)
    ref = paged_attention_ref(q, kp, vp, jnp.asarray(tables),
                              jnp.asarray(lens))
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_paged_matches_dense_attention():
    """Paged attention over scattered pages == dense attention over the
    same logical sequence (the MMU indirection is value-invisible)."""
    b, h, kh, d, page, maxp, npages = 2, 4, 2, 64, 32, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    lens = np.array([100, 57], np.int32)
    tables = _tables(b, page, maxp, npages, lens)
    kd = jax.random.normal(ks[1], (b, maxp * page, kh, d), jnp.float32)
    vd = jax.random.normal(ks[2], (b, maxp * page, kh, d), jnp.float32)
    # scatter the dense kv into pages per the tables
    kp = jnp.zeros((npages, kh, page, d), jnp.float32)
    vp = jnp.zeros((npages, kh, page, d), jnp.float32)
    for i in range(b):
        for vp_i in range(maxp):
            pp = tables[i, vp_i]
            if pp < 0:
                continue
            sl = slice(vp_i * page, (vp_i + 1) * page)
            kp = kp.at[pp].set(kd[i, sl].swapaxes(0, 1))
            vp = vp.at[pp].set(vd[i, sl].swapaxes(0, 1))
        # dense ref per row (pages are per-row exclusive in this test)
        q = jax.random.normal(ks[0], (1, h, d), jnp.float32)
        out = paged_attention(q, kp, vp, jnp.asarray(tables[i:i+1]),
                              jnp.asarray(lens[i:i+1]), interpret=True)
        qr = q.reshape(1, h, 1, d).transpose(0, 1, 2, 3)
        ref = attention_ref(q[:, :, None], kd[i:i+1].transpose(0, 2, 1, 3),
                            vd[i:i+1].transpose(0, 2, 1, 3),
                            causal=False)[:, :, 0]
        # mask to lens[i]: rebuild ref with masked attention
        ref = paged_attention_ref(q, kp, vp, jnp.asarray(tables[i:i+1]),
                                  jnp.asarray(lens[i:i+1]))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)


# =================================================================== ssd ===
SSD_CASES = [
    (2, 128, 4, 64, 1, 32, 32),
    (1, 200, 8, 64, 2, 64, 64),     # padded seq
    (2, 256, 4, 32, 4, 16, 128),
]


@pytest.mark.parametrize("case", SSD_CASES,
                         ids=[f"ssd{i}" for i in range(len(SSD_CASES))])
def test_ssd_kernel_matches_sequential(case):
    b, s, h, p, g, n, chunk = case
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    Bm = jax.random.normal(ks[3], (b, s, g, n)) * 0.3
    C = jax.random.normal(ks[4], (b, s, g, n)) * 0.3
    y_ref, st_ref = ssd_sequential(x, dt, A, Bm, C)
    y_chk, st_chk = ssd_chunked(x, dt, A, Bm, C, chunk=chunk)
    y_pal, st_pal = ssd_chunked_pallas(x, dt, A, Bm, C, chunk=chunk,
                                       interpret=True)
    np.testing.assert_allclose(np.asarray(y_chk), np.asarray(y_ref),
                               atol=5e-4)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               atol=5e-4)
    np.testing.assert_allclose(np.asarray(st_pal), np.asarray(st_ref),
                               atol=5e-4)


@settings(max_examples=8, deadline=None)
@given(s=st.integers(8, 96), chunk=st.sampled_from([8, 16, 32]))
def test_ssd_chunk_invariance(s, chunk):
    """Property: the chunked algorithm is exact for ANY chunk size."""
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(ks[0], (1, s, 2, 16), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, s, 2)))
    A = -jnp.exp(jax.random.normal(ks[2], (2,)) * 0.5)
    Bm = jax.random.normal(ks[3], (1, s, 1, 8)) * 0.3
    C = jax.random.normal(ks[4], (1, s, 1, 8)) * 0.3
    y1, st1 = ssd_sequential(x, dt, A, Bm, C)
    y2, st2 = ssd_chunked(x, dt, A, Bm, C, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y1), atol=5e-4)
    np.testing.assert_allclose(np.asarray(st2), np.asarray(st1), atol=5e-4)


def test_ssd_decode_continuation():
    """Chunked prefill state + single-token decode == longer sequential."""
    from repro.models.ssm import ssd_decode
    ks = jax.random.split(jax.random.PRNGKey(6), 5)
    b, s, h, p, g, n = 1, 33, 2, 16, 1, 8
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    Bm = jax.random.normal(ks[3], (b, s, g, n)) * 0.3
    C = jax.random.normal(ks[4], (b, s, g, n)) * 0.3
    y_all, _ = ssd_sequential(x, dt, A, Bm, C)
    _, st = ssd_chunked(x[:, :-1], dt[:, :-1], A, Bm[:, :-1], C[:, :-1],
                        chunk=16)
    y_last, _ = ssd_decode(x[:, -1], dt[:, -1], A, Bm[:, -1], C[:, -1], st)
    np.testing.assert_allclose(np.asarray(y_last),
                               np.asarray(y_all[:, -1]), atol=5e-4)


# ============================================================ flash bwd ====
BWD_CASES = [
    (1, 4, 2, 128, 128, 64, True, 0),
    (2, 2, 1, 96, 160, 64, True, 0),     # padded + MHA-as-GQA
    (1, 4, 4, 128, 128, 64, False, 0),   # non-causal
    (1, 2, 2, 128, 128, 64, True, 64),   # sliding window
]


@pytest.mark.parametrize("case", BWD_CASES,
                         ids=[f"fabwd{i}" for i in range(len(BWD_CASES))])
def test_flash_attention_bwd_matches_grad_of_ref(case):
    from repro.kernels.flash_attention.flash_attention_bwd import (
        flash_attention_bwd)
    b, h, kh, sq, sk, d, causal, window = case
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (b, h, sq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, kh, sk, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, kh, sk, d), jnp.float32)
    do = jax.random.normal(ks[3], (b, h, sq, d), jnp.float32)

    def f(q, k, v):
        return jnp.sum(attention_ref(q, k, v, causal=causal,
                                     window=window) * do)
    dq_r, dk_r, dv_r = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    o, lse = flash_attention(q, k, v, causal=causal, window=window,
                             interpret=True, return_lse=True)
    dq, dk, dv = flash_attention_bwd(q, k, v, o, do, lse, causal=causal,
                                     window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(dq_r), atol=5e-4)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dk_r), atol=5e-4)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(dv_r), atol=5e-4)


def test_mha_fused_custom_vjp_end_to_end():
    from repro.kernels.flash_attention.ops import mha_fused
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 128, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 128, 64), jnp.float32)

    def loss_fused(q, k, v):
        return jnp.sum(mha_fused(q, k, v, True, 0, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_ref(q, k, v, causal=True) ** 2)

    g1 = jax.grad(loss_fused, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)
