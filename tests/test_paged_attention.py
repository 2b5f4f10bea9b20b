"""Paged flash-decode kernel: dense-MHA equivalence across page sizes,
ragged per-request lengths, preemption-reshuffled block tables, int8
pools, and an end-to-end engine check on the kernel path.

The oracle chain: ops.paged_attention (in-kernel block-table gather) ==
ref.paged_attention_ref (dense gather + masked softmax) == ref.mha_ref
(plain dense attention on the contiguously laid-out cache). All
comparisons are fp32-tolerance — the kernel's per-page online softmax
reorders the accumulation vs the one-shot dense softmax, so bit equality
is not the contract (see test_kv_cache.py for the exact-token bookkeeping
tests, which pin the gather path)."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels import paged_attention as pa
from repro.runtime.kv_cache import SCRATCH_PAGE, PageAllocator


def _paged_case(key, B, H, KV, D, page, n_blocks, lengths, dtype=jnp.float32,
                shuffle_key=None):
    """Build (q, pools, block_table, dense_k, dense_v) where request b's
    tokens 0..lengths[b]-1 are laid out contiguously in dense_k/v and
    scattered page-by-page into the pools via a (optionally shuffled)
    block table. Unowned table entries point at the scratch page."""
    P = 1 + B * n_blocks                       # page 0 = scratch
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32).astype(dtype)
    S = n_blocks * page
    dense_k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
    dense_v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)
    phys = np.arange(1, P, dtype=np.int32)
    if shuffle_key is not None:
        phys = np.asarray(jax.random.permutation(shuffle_key, phys))
    table = np.full((B, n_blocks), SCRATCH_PAGE, np.int32)
    kp = np.zeros((P, page, KV, D), np.float32)
    vp = np.zeros((P, page, KV, D), np.float32)
    nxt = 0
    for b in range(B):
        for j in range(-(-int(lengths[b]) // page)):
            pid = int(phys[nxt]); nxt += 1
            table[b, j] = pid
            kp[pid] = np.asarray(dense_k[b, j * page:(j + 1) * page])
            vp[pid] = np.asarray(dense_v[b, j * page:(j + 1) * page])
    return (q, jnp.asarray(kp).astype(dtype), jnp.asarray(vp).astype(dtype),
            jnp.asarray(table), dense_k.astype(dtype), dense_v.astype(dtype))


@pytest.mark.parametrize("page", [8, 16, 64])
def test_matches_dense_mha_across_page_sizes(page):
    """Kernel output == plain dense MHA over the contiguous cache, for
    every page size the serving engine uses."""
    B, H, KV, D, n_blocks = 3, 8, 2, 32, 128 // page
    lengths = [5, 97, 128][:B]
    lengths = [min(n, n_blocks * page) for n in lengths]
    q, kp, vp, table, dk, dv = _paged_case(
        jax.random.key(page), B, H, KV, D, page, n_blocks, lengths)
    got = ops.paged_attention(q, kp, vp, table, jnp.asarray(lengths))
    for b in range(B):
        want = ref.mha_ref(q[b][None, None], dk[b][None], dv[b][None],
                           causal=False, kv_valid=lengths[b])[0, 0]
        np.testing.assert_allclose(np.asarray(got[b]), np.asarray(want),
                                   rtol=3e-3, atol=3e-3)


def test_ragged_lengths_ignore_pool_garbage():
    """Positions past each request's length — including whole scratch-page
    blocks — must contribute zero probability mass."""
    B, H, KV, D, page, n_blocks = 4, 4, 4, 16, 8, 4
    lengths = [1, 7, 9, 32]
    key = jax.random.key(1)
    q, kp, vp, table, dk, dv = _paged_case(key, B, H, KV, D, page, n_blocks,
                                           lengths)
    got = ops.paged_attention(q, kp, vp, table, jnp.asarray(lengths))
    # poison everything the lengths say is dead: unwritten pool rows AND
    # the scratch page; output must not move at all
    kp2 = kp.at[SCRATCH_PAGE].set(1e4)
    vp2 = vp.at[SCRATCH_PAGE].set(1e4)
    for b, n in enumerate(lengths):
        blk, off = n // page, n % page
        if off:
            kp2 = kp2.at[table[b, blk], off:].set(1e4)
            vp2 = vp2.at[table[b, blk], off:].set(1e4)
    got2 = ops.paged_attention(q, kp2, vp2, table, jnp.asarray(lengths))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(got2))


def test_preemption_reshuffled_block_table():
    """After preempt + resume the allocator hands back DIFFERENT physical
    pages (LIFO free list); same logical contents under a reshuffled
    table must give identical outputs."""
    B, H, KV, D, page, n_blocks = 3, 6, 3, 16, 8, 4
    lengths = [9, 17, 25]
    key = jax.random.key(2)
    q, kp1, vp1, t1, _, _ = _paged_case(key, B, H, KV, D, page, n_blocks,
                                        lengths)
    q2, kp2, vp2, t2, _, _ = _paged_case(key, B, H, KV, D, page, n_blocks,
                                         lengths,
                                         shuffle_key=jax.random.key(3))
    assert not np.array_equal(np.asarray(t1), np.asarray(t2))
    a = ops.paged_attention(q, kp1, vp1, t1, jnp.asarray(lengths))
    b = ops.paged_attention(q2, kp2, vp2, t2, jnp.asarray(lengths))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_allocator_tables_drive_kernel():
    """Reuse the PageAllocator harness: allocate/extend/free/re-allocate,
    then run the kernel on the resulting (fragmented) tables."""
    page, n_blocks = 8, 4
    B, H, KV, D = 2, 4, 2, 16
    a = PageAllocator(2 * n_blocks, page)
    a.allocate(0, 12)                 # 2 pages
    a.allocate(1, 20)                 # 3 pages
    a.free_request(0)                 # rid 0 preempted
    a.allocate(2, 10)                 # resumes into rid 0's LIFO'd pages
    a.check_no_aliasing()
    lengths = [a.tokens(2), a.tokens(1)]
    rows = np.full((B, n_blocks), SCRATCH_PAGE, np.int32)
    for i, rid in enumerate((2, 1)):
        t = a.block_table(rid)
        rows[i, :len(t)] = t
    key = jax.random.key(4)
    kp = jax.random.normal(key, (1 + 2 * n_blocks, page, KV, D), jnp.float32)
    vp = jax.random.normal(jax.random.key(5), kp.shape, jnp.float32)
    q = jax.random.normal(jax.random.key(6), (B, H, D), jnp.float32)
    got = ops.paged_attention(q, kp, vp, jnp.asarray(rows),
                              jnp.asarray(lengths))
    want = ref.paged_attention_ref(q, kp, vp, jnp.asarray(rows),
                                   jnp.asarray(lengths))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-3, atol=3e-3)


def test_int8_pool_dequant_in_kernel():
    B, H, KV, D, page, n_blocks = 2, 8, 2, 32, 16, 2
    lengths = [13, 32]
    q, kp, vp, table, _, _ = _paged_case(jax.random.key(7), B, H, KV, D,
                                         page, n_blocks, lengths)
    scale = 8.0
    kq = jnp.clip(jnp.round(kp * 127 / scale), -127, 127).astype(jnp.int8)
    vq = jnp.clip(jnp.round(vp * 127 / scale), -127, 127).astype(jnp.int8)
    got = ops.paged_attention(q, kq, vq, table, jnp.asarray(lengths),
                              kv_scale=scale)
    want = ref.paged_attention_ref(q, kq, vq, table, jnp.asarray(lengths),
                                   kv_scale=scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-3, atol=3e-3)


@settings(max_examples=10, deadline=None)
@given(
    page=st.sampled_from([8, 16]),
    n_blocks=st.integers(min_value=1, max_value=4),
    kv=st.sampled_from([1, 2, 4]),
    group=st.sampled_from([1, 2, 4]),
    seed=st.integers(min_value=0, max_value=2 ** 16),
    data=st.data(),
)
def test_property_kernel_matches_ref(page, n_blocks, kv, group, seed, data):
    """Property: for random shapes, tables and ragged lengths, the kernel
    matches the dense-gather oracle to fp32 tolerance. Skips cleanly when
    hypothesis is absent (tests/conftest.py stub)."""
    B, D = 2, 16
    lengths = [data.draw(st.integers(min_value=1,
                                     max_value=page * n_blocks))
               for _ in range(B)]
    q, kp, vp, table, _, _ = _paged_case(
        jax.random.key(seed), B, kv * group, kv, D, page, n_blocks, lengths,
        shuffle_key=jax.random.key(seed + 1))
    got = ops.paged_attention(q, kp, vp, table, jnp.asarray(lengths))
    want = ref.paged_attention_ref(q, kp, vp, table, jnp.asarray(lengths))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-3, atol=3e-3)


# ---------------------------------------------------------------------------
# Multi-token query blocks (speculative verify: q rows per request > 1)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("page", [8, 16, 64])
@pytest.mark.parametrize("T", [2, 5])
def test_multi_token_block_matches_dense_mha(page, T):
    """A T-row query block must equal T independent causal rows of dense
    MHA: row t (absolute position base + t) sees exactly base + t + 1
    keys. Lengths chosen so blocks straddle page boundaries (base % page
    walks the whole row range) across every serving page size."""
    B, H, KV, D, n_blocks = 3, 8, 2, 32, 128 // page
    lengths = [T + 1, page + T // 2 + 1, 2 * page + T][:B]   # incl. T rows
    lengths = [min(n, n_blocks * page) for n in lengths]
    key = jax.random.key(page + T)
    q, kp, vp, table, dk, dv = _paged_case(
        key, B, T * H, KV, D, page, n_blocks, lengths)
    # _paged_case builds (B, T*H, D) q; reinterpret as (B, T, H, D) rows
    q = q.reshape(B, T, H, D)
    got = ops.paged_attention(q, kp, vp, table, jnp.asarray(lengths))
    assert got.shape == (B, T, H, D)
    for b in range(B):
        for t in range(T):
            want = ref.mha_ref(q[b, t][None, None], dk[b][None], dv[b][None],
                               causal=False,
                               kv_valid=lengths[b] - T + t + 1)[0, 0]
            np.testing.assert_allclose(np.asarray(got[b, t]),
                                       np.asarray(want),
                                       rtol=3e-3, atol=3e-3)


def test_multi_token_block_matches_paged_ref():
    """Kernel vs the generalized dense-gather oracle on ragged lengths and
    a shuffled (preemption-shaped) block table."""
    B, H, KV, D, page, n_blocks, T = 3, 6, 3, 16, 8, 4, 3
    lengths = [4, 17, 30]
    q, kp, vp, table, _, _ = _paged_case(
        jax.random.key(11), B, T * H, KV, D, page, n_blocks, lengths,
        shuffle_key=jax.random.key(12))
    q = q.reshape(B, T, H, D)
    got = ops.paged_attention(q, kp, vp, table, jnp.asarray(lengths))
    want = ref.paged_attention_ref(q, kp, vp, table, jnp.asarray(lengths))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-3, atol=3e-3)


def test_multi_token_rows_ignore_pool_garbage():
    """Rows past each query row's causal horizon — including the rows the
    block itself occupies — must contribute zero probability mass: row t
    may see rows 0..base+t, never base+t+1..base+T-1."""
    B, H, KV, D, page, n_blocks, T = 2, 4, 2, 16, 8, 3, 4
    lengths = [6, 21]
    key = jax.random.key(13)
    q, kp, vp, table, _, _ = _paged_case(key, B, T * H, KV, D, page,
                                         n_blocks, lengths)
    q = q.reshape(B, T, H, D)
    got = ops.paged_attention(q, kp, vp, table, jnp.asarray(lengths))
    # poison everything at positions >= each row's own horizon is not
    # possible per-row in one pool, but poisoning past lengths[b]-1 (the
    # LAST row's horizon) plus the scratch page must leave every row
    # unchanged; per-row causality is pinned by the dense-mha test above
    kp2, vp2 = kp.at[SCRATCH_PAGE].set(1e4), vp.at[SCRATCH_PAGE].set(1e4)
    for b, n in enumerate(lengths):
        blk, off = n // page, n % page
        if off:
            kp2 = kp2.at[table[b, blk], off:].set(1e4)
            vp2 = vp2.at[table[b, blk], off:].set(1e4)
    got2 = ops.paged_attention(q, kp2, vp2, table, jnp.asarray(lengths))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(got2))


def test_single_token_block_is_bitwise_the_3d_path():
    """(B, 1, H, D) q must reduce EXACTLY to the (B, H, D) kernel — the
    T=1 serving path pays nothing for the generalization."""
    B, H, KV, D, page, n_blocks = 3, 8, 2, 32, 16, 4
    lengths = [5, 33, 64]
    q, kp, vp, table, _, _ = _paged_case(jax.random.key(14), B, H, KV, D,
                                         page, n_blocks, lengths)
    a = ops.paged_attention(q, kp, vp, table, jnp.asarray(lengths))
    b = ops.paged_attention(q[:, None], kp, vp, table, jnp.asarray(lengths))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b[:, 0]))


def test_multi_token_int8_pool():
    B, H, KV, D, page, n_blocks, T = 2, 8, 2, 32, 16, 2, 3
    lengths = [13, 32]
    q, kp, vp, table, _, _ = _paged_case(jax.random.key(15), B, T * H, KV,
                                         D, page, n_blocks, lengths)
    q = q.reshape(B, T, H, D)
    scale = 8.0
    kq = jnp.clip(jnp.round(kp * 127 / scale), -127, 127).astype(jnp.int8)
    vq = jnp.clip(jnp.round(vp * 127 / scale), -127, 127).astype(jnp.int8)
    got = ops.paged_attention(q, kq, vq, table, jnp.asarray(lengths),
                              kv_scale=scale)
    want = ref.paged_attention_ref(q, kq, vq, table, jnp.asarray(lengths),
                                   kv_scale=scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-3, atol=3e-3)


@settings(max_examples=10, deadline=None)
@given(
    page=st.sampled_from([8, 16]),
    n_blocks=st.integers(min_value=1, max_value=4),
    t_rows=st.integers(min_value=1, max_value=4),
    group=st.sampled_from([1, 2]),
    seed=st.integers(min_value=0, max_value=2 ** 16),
    data=st.data(),
)
def test_property_multi_token_matches_ref(page, n_blocks, t_rows, group,
                                          seed, data):
    """Property: random shapes, T-row blocks, tables and ragged lengths —
    kernel == dense-gather oracle to fp32 tolerance."""
    B, KV, D = 2, 2, 16
    H = KV * group
    lengths = [data.draw(st.integers(min_value=t_rows,
                                     max_value=page * n_blocks))
               for _ in range(B)]
    q, kp, vp, table, _, _ = _paged_case(
        jax.random.key(seed), B, t_rows * H, KV, D, page, n_blocks, lengths,
        shuffle_key=jax.random.key(seed + 1))
    q = q.reshape(B, t_rows, H, D)
    got = ops.paged_attention(q, kp, vp, table, jnp.asarray(lengths))
    want = ref.paged_attention_ref(q, kp, vp, table, jnp.asarray(lengths))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-3, atol=3e-3)


# ---------------------------------------------------------------------------
# Sliding windows (hybrid local_attn layers: in-sweep window masking +
# below-window page skipping; ISSUE 5)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window,page", [(4, 8), (16, 4), (7, 8), (32, 16)])
def test_windowed_matches_hand_sliced_dense(window, page):
    """Kernel with a window must equal plain dense attention over exactly
    the last `window` keys — across windows smaller than, equal to, and
    straddling the page size."""
    B, H, KV, D, n_blocks = 3, 8, 2, 32, 4
    lengths = [2, page + 1, min(3 * page + 2, n_blocks * page)][:B]
    q, kp, vp, table, dk, dv = _paged_case(
        jax.random.key(window * 100 + page), B, H, KV, D, page, n_blocks,
        lengths)
    got = ops.paged_attention(q, kp, vp, table, jnp.asarray(lengths),
                              window=window)
    for b, n in enumerate(lengths):
        lo = max(0, n - window)
        want = ref.mha_ref(q[b][None, None], dk[b][None, lo:n],
                           dv[b][None, lo:n], causal=False)[0, 0]
        np.testing.assert_allclose(np.asarray(got[b]), np.asarray(want),
                                   rtol=3e-3, atol=3e-3)


@pytest.mark.parametrize("T", [2, 4])
def test_windowed_multi_token_matches_ref(T):
    """T-row verify blocks under a window: row t sees keys in
    (base + t - window, base + t] — kernel == generalized oracle."""
    B, H, KV, D, page, n_blocks, window = 2, 6, 3, 16, 8, 4, 5
    lengths = [T + 1, 3 * page + T]
    q, kp, vp, table, _, _ = _paged_case(
        jax.random.key(21 + T), B, T * H, KV, D, page, n_blocks, lengths,
        shuffle_key=jax.random.key(22))
    q = q.reshape(B, T, H, D)
    got = ops.paged_attention(q, kp, vp, table, jnp.asarray(lengths),
                              window=window)
    want = ref.paged_attention_ref(q, kp, vp, table, jnp.asarray(lengths),
                                   window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-3, atol=3e-3)


def test_windowed_ignores_below_window_pages():
    """Pages entirely below the window are skipped in-grid AND masked
    in-sweep: poisoning every below-window key — including replacing
    whole recycled pages with the scratch page, as the serving engine
    does — must not move the output at all."""
    B, H, KV, D, page, n_blocks, window = 2, 4, 2, 16, 4, 8, 6
    lengths = [13, 29]
    q, kp, vp, table, _, _ = _paged_case(jax.random.key(31), B, H, KV, D,
                                         page, n_blocks, lengths)
    got = ops.paged_attention(q, kp, vp, table, jnp.asarray(lengths),
                              window=window)
    kp2, vp2 = kp.at[SCRATCH_PAGE].set(1e4), vp.at[SCRATCH_PAGE].set(1e4)
    table2 = np.asarray(table).copy()
    for b, n in enumerate(lengths):
        lo = n - window                      # first visible key position
        for p_ in range(max(lo, 0)):
            kp2 = kp2.at[table[b, p_ // page], p_ % page].set(1e4)
            vp2 = vp2.at[table[b, p_ // page], p_ % page].set(1e4)
        # recycle: whole blocks below the window point at scratch
        dead = max(0, lo) // page
        table2[b, :dead] = SCRATCH_PAGE
    got2 = ops.paged_attention(q, kp2, vp2, table, jnp.asarray(lengths),
                               window=window)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(got2))
    got3 = ops.paged_attention(q, kp2, vp2, jnp.asarray(table2),
                               jnp.asarray(lengths), window=window)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(got3))


def test_windowed_int8_pool():
    B, H, KV, D, page, n_blocks, window = 2, 8, 2, 32, 8, 3, 10
    lengths = [7, 23]
    q, kp, vp, table, _, _ = _paged_case(jax.random.key(41), B, H, KV, D,
                                         page, n_blocks, lengths)
    scale = 8.0
    kq = jnp.clip(jnp.round(kp * 127 / scale), -127, 127).astype(jnp.int8)
    vq = jnp.clip(jnp.round(vp * 127 / scale), -127, 127).astype(jnp.int8)
    got = ops.paged_attention(q, kq, vq, table, jnp.asarray(lengths),
                              kv_scale=scale, window=window)
    want = ref.paged_attention_ref(q, kq, vq, table, jnp.asarray(lengths),
                                   kv_scale=scale, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-3, atol=3e-3)


def test_windowed_gather_oracle_matches_ref():
    """layers.attend_decode with window (ring=False, absolute positions)
    is the paged gather baseline's masking — it must agree with the
    dense-gather oracle for T == 1 and T > 1."""
    from repro.models.layers import attend_decode
    B, H, KV, D, page, n_blocks, window, T = 2, 4, 2, 16, 8, 3, 5, 3
    lengths = [T + 2, 2 * page + T]
    q, kp, vp, table, _, _ = _paged_case(jax.random.key(51), B, T * H, KV,
                                         D, page, n_blocks, lengths)
    q = q.reshape(B, T, H, D)
    kg = kp[table].reshape(B, n_blocks * page, KV, D)
    vg = vp[table].reshape(B, n_blocks * page, KV, D)
    pos = jnp.asarray(lengths) - T          # first new token's position
    got = attend_decode(q, kg, vg, pos, window=window)
    want = ref.paged_attention_ref(q, kp, vp, table, jnp.asarray(lengths),
                                   window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-3, atol=3e-3)


@settings(max_examples=10, deadline=None)
@given(
    page=st.sampled_from([4, 8]),
    n_blocks=st.integers(min_value=1, max_value=4),
    t_rows=st.integers(min_value=1, max_value=3),
    window=st.integers(min_value=1, max_value=24),
    seed=st.integers(min_value=0, max_value=2 ** 16),
    data=st.data(),
)
def test_property_windowed_matches_ref(page, n_blocks, t_rows, window,
                                       seed, data):
    """Property: random shapes, T-row blocks, windows and ragged lengths
    — windowed kernel == windowed dense-gather oracle."""
    B, KV, D = 2, 2, 16
    H = KV * 2
    lengths = [data.draw(st.integers(min_value=t_rows,
                                     max_value=page * n_blocks))
               for _ in range(B)]
    q, kp, vp, table, _, _ = _paged_case(
        jax.random.key(seed), B, t_rows * H, KV, D, page, n_blocks, lengths,
        shuffle_key=jax.random.key(seed + 1))
    q = q.reshape(B, t_rows, H, D)
    got = ops.paged_attention(q, kp, vp, table, jnp.asarray(lengths),
                              window=window)
    want = ref.paged_attention_ref(q, kp, vp, table, jnp.asarray(lengths),
                                   window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-3, atol=3e-3)


# ---------------------------------------------------------------------------
# Multi-page KV blocks: ppb pages stream per block, double-buffered across
# blocks and requests, nothing fetched past a request's live keys
# ---------------------------------------------------------------------------


def _blocked(q, kp, vp, table, lengths, ppb, **kw):
    """The kernel at an explicit pages-per-block (the public entry point
    derives it from the pool's shapes)."""
    q4 = q if q.ndim == 4 else q[:, None]
    out = pa._paged(q4, kp, vp, table, jnp.asarray(lengths, jnp.int32),
                    kv_scale=kw.get("kv_scale"), window=kw.get("window", 0),
                    ppb=ppb, interpret=True)
    return out if q.ndim == 4 else out[:, 0]


def _poison(kp, vp, table, lengths, value):
    """Set every pool row no request may read to ``value``: the scratch
    page, the unwritten tail of each last page, and unowned pages."""
    page = kp.shape[1]
    owned = {int(p_) for p_ in np.asarray(table).ravel()}
    dead = [p_ for p_ in range(kp.shape[0]) if p_ not in owned]
    kp2 = kp.at[SCRATCH_PAGE].set(value).at[jnp.asarray(dead)].set(value)
    vp2 = vp.at[SCRATCH_PAGE].set(value).at[jnp.asarray(dead)].set(value)
    for b, n in enumerate(lengths):
        blk, off = n // page, n % page
        if off:
            kp2 = kp2.at[table[b, blk], off:].set(value)
            vp2 = vp2.at[table[b, blk], off:].set(value)
    return kp2, vp2


@pytest.mark.parametrize("ppb", [1, 3, 8])
def test_block_sizes_match_ref_and_ignore_poison(ppb):
    """Every block size, including one page and the whole table: lengths
    of 1, exactly one block, one block + 1 and max_len side by side with
    a dead slot (length 0) and an engine-style dead slot (all-scratch
    table, length 1). Live rows match the oracle; a pool whose
    unreadable rows are NaN or Inf gives bit-identical outputs."""
    B, H, KV, D, page, n_blocks = 6, 4, 2, 16, 8, 8
    bk, max_len = ppb * page, n_blocks * page
    lengths = [1, bk, min(bk + 1, max_len), max_len, 0, 1]
    q, kp, vp, table, _, _ = _paged_case(
        jax.random.key(60 + ppb), B, H, KV, D, page, n_blocks,
        lengths[:4] + [0, 0], shuffle_key=jax.random.key(61))
    got = _blocked(q, kp, vp, table, lengths, ppb)
    want = ref.paged_attention_ref(q, kp, vp, table, jnp.asarray(lengths))
    np.testing.assert_allclose(np.asarray(got[:4]), np.asarray(want[:4]),
                               rtol=3e-3, atol=3e-3)
    assert np.all(np.asarray(got[4]) == 0)
    assert np.all(np.isfinite(np.asarray(got)))
    for value in (jnp.nan, jnp.inf):
        kp2, vp2 = _poison(kp, vp, table, lengths, value)
        got2 = _blocked(q, kp2, vp2, table, lengths, ppb)
        np.testing.assert_array_equal(np.asarray(got[:4]),
                                      np.asarray(got2[:4]))


@pytest.mark.parametrize("ppb", [2, 4])
@pytest.mark.parametrize("variant", ["T3", "int8", "window", "window-T2"])
def test_multi_page_variants_match_ref(variant, ppb):
    """Speculative T-row blocks, int8 pools and sliding windows through
    several multi-page blocks per request, on a NaN-poisoned pool (with
    a window, the recycled lead blocks point at the NaN scratch page)."""
    B, H, KV, D, page, n_blocks = 3, 4, 2, 16, 4, 12
    T = {"T3": 3, "window-T2": 2}.get(variant, 1)
    window = 6 if variant.startswith("window") else 0
    lengths = [T + 1, 2 * ppb * page + 1, n_blocks * page]
    q, kp, vp, table, _, _ = _paged_case(
        jax.random.key(70 + ppb), B, T * H, KV, D, page, n_blocks, lengths,
        shuffle_key=jax.random.key(71))
    q = q.reshape(B, T, H, D) if T > 1 else q
    kw = {"window": window}
    if variant == "int8":
        kw["kv_scale"] = 8.0
        kp = jnp.clip(jnp.round(kp * 127 / 8.0), -127, 127).astype(jnp.int8)
        vp = jnp.clip(jnp.round(vp * 127 / 8.0), -127, 127).astype(jnp.int8)
    want = ref.paged_attention_ref(q, kp, vp, table, jnp.asarray(lengths),
                                   **kw)
    if variant != "int8":              # int8 holds no NaN
        kp, vp = _poison(kp, vp, table, lengths, jnp.nan)
    if window:
        table = np.asarray(table).copy()
        for b, n in enumerate(lengths):
            table[b, :max(0, n - T - window + 1) // page] = SCRATCH_PAGE
        table = jnp.asarray(table)
    got = _blocked(q, kp, vp, table, lengths, ppb, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-3, atol=3e-3)


def test_pages_per_block_from_shapes():
    """ppb targets a fixed K block size from page x KV x D x itemsize,
    capped by the table width and by a window's pages."""
    blk = pa._BLOCK_BYTES
    assert pa.pages_per_block(16, 2, 128, 2, 128) == blk // (16 * 2 * 128 * 2)
    assert pa.pages_per_block(16, 8, 64, 2, 256) == blk // (16 * 8 * 64 * 2)
    assert pa.pages_per_block(16, 2, 128, 1, 128) == \
        2 * pa.pages_per_block(16, 2, 128, 2, 128)
    assert pa.pages_per_block(16, 2, 128, 2, 3) == 3
    assert pa.pages_per_block(16, 1, 256, 2, 128, window=40) == 3
    assert pa.pages_per_block(16, 64, 256, 4, 128) == 1


# ---------------------------------------------------------------------------
# End-to-end: the serving engine on the kernel path
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_kernel_engine_matches_dense_engine_fp32():
    """With float32 weights the accumulation-order wobble is ~1e-6, far
    below any logit gap — so the kernel-path engine must reproduce the
    dense engine's greedy tokens exactly, through admission, page growth,
    preemption and resume."""
    import dataclasses
    from repro.configs import get_smoke_config
    from repro.models import api
    from repro.runtime.scheduler import Scheduler
    from repro.runtime.serving import (DenseServingEngine,
                                       PagedServingEngine, Request)
    cfg = dataclasses.replace(get_smoke_config("qwen2.5-3b"),
                              dtype="float32")
    params = api.init_params(cfg, jax.random.key(0))

    def mk():
        return [Request(rid=0, prompt=[5, 4, 3, 2, 1, 6, 7], max_new=8),
                Request(rid=1, prompt=[1, 2, 3, 4, 5, 6], max_new=8)]

    dense = DenseServingEngine(cfg, params, slots=2, max_len=32)
    want = {r.rid: r.generated
            for r in dense.run_to_completion(mk(), max_steps=60)}

    eng = PagedServingEngine(cfg, params, slots=2, max_len=32, page_size=4,
                             num_pages=4, attn_impl="kernel")
    reqs = mk()
    sched = Scheduler(eng)
    for r in reqs:
        sched.add(r)
    sched.drain(max_steps=400)
    assert sched.preempted >= 1          # the pool is sized to force it
    assert {r.rid: r.generated for r in reqs} == want
    eng.alloc.check_no_aliasing()
    assert eng.alloc.allocated_pages == 0
