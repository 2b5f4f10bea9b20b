"""Paged flash-decode attention kernel — the in-kernel block-table gather.

The serving engine's paged KV cache (repro.runtime.kv_cache) keeps every
layer's K/V in a shared page pool ``(P, page, KV, D)`` addressed through
per-request block tables. The PR-1 decode path gathered each request's
pages into a dense ``(B, n_blocks * page, KV, D)`` buffer per layer before
attending — exactly the "separated memory" data movement the paper's
shared-memory streamers avoid (PAPER.md §III: the flexible streamers fetch
the tiles the PEs consume, nothing else). This kernel moves the block-table
indirection *inside* the attention kernel, vLLM-style:

* the block table and per-request valid lengths ride in as **scalar
  prefetch** operands (``pltpu.PrefetchScalarGridSpec``); the K/V pools
  stay in HBM and the kernel issues its own DMAs, one per pool page —
  the pool's contiguous unit, so each copy is a single dense transfer,
  never a strided per-head slice;
* **multi-page KV blocks**: the unit of streaming is a block of ``ppb``
  pages (``pages_per_block``: about ``_BLOCK_BYTES`` of K per block,
  taken from ``page x KV x D x itemsize``, capped by the table width and
  by the window). All of a block's page copies are in flight together
  into a VMEM slab; a block whose pages were all fetched is waited for
  once;
* **double buffering**: two slabs per pool. The next block's copies start
  before the current block is computed — across grid steps too: the
  last block of request ``b`` starts the first block of request ``b+1``
  (an SMEM carry says which slab it landed in), so the stream does not
  drain between requests;
* **live length bound**: the grid walks requests; inside, a ``fori_loop``
  runs over ``cdiv(length, ppb*page)`` blocks only, so blocks past a
  request's length are neither fetched nor stepped, and within the last
  block only pages holding live keys are fetched. Stale VMEM and never-
  written page tails are kept out of the sums twice: scores are masked
  before the softmax, and V rows outside the live key range are zeroed
  before ``p @ v`` (``0 * NaN`` is NaN);
* GQA without padded per-head tiles: a page is viewed as rows of whole
  lane tiles (``pool_rows``): one row per token and head where D is
  a multiple of 128 (a free view of the pool's layout), one row per
  token holding all KV heads otherwise. Each query row carries its D
  values at its head's lanes and zeros elsewhere, so all ``H = KV*G``
  query rows score against the slab in one contraction; scores of a
  query head against another head's rows are masked out. The matrix unit
  does the extra products, which are cheap next to the bytes a decode
  step moves;
* **multi-token query blocks** (speculative decode): ``q`` may carry
  ``T >= 1`` rows per request. The T axis is folded into the head-group
  axis — row ``r = h*T*G + t*G + g`` — so every row rides the SAME
  streamed block (the verify step multiplies arithmetic intensity by T at
  unchanged KV traffic). Causality is enforced in-sweep: query row ``t``
  sits at absolute position ``base + t`` (``base = lengths[b] - T``) and
  sees exactly ``base + t + 1`` keys;
* **sliding windows** (hybrid stacks, ``local_attn`` layers): a static
  ``window`` bounds how far back each query row may look. Blocks wholly
  below the earliest row's window start are skipped, and so are the
  pages of the straddling block below it — paired with the engine's page
  recycling (``runtime/kv_cache.release_prefix``) the sweep costs
  O(window) per request however long its logical context grows — and an
  extra mask term trims the straddling keys (key position must exceed
  ``base + t - window``);
* int8 KV pools are dequantized slab-by-slab inside the kernel
  (``kv_scale``), so the f32 view of the cache never materializes either;
* **tensor parallelism** (ISSUE 6) needs NO kernel change: under the
  serving TP plan (``parallel/tp.py``) this kernel runs inside a
  ``shard_map`` body on each shard's LOCAL slice of the pool — ``KV`` is
  the per-shard KV-head count, ``q`` carries the matching ``H/M`` heads,
  and because GQA groups shard whole (heads and kv_heads divide the mesh
  together), ``H % KV == 0`` and the group fold are unchanged. Block
  tables and lengths are replicated, pages are local, and the online-
  softmax scratch never crosses shards — the psum happens later, at the
  out-projection (``layers.attention_decode``).

The pure-jnp oracle (dense gather + masked softmax) is
``repro.kernels.ref.paged_attention_ref``; dispatch (TPU compiled vs
interpret elsewhere) is ``repro.kernels.ops.paged_attention``. See
DESIGN.md "Paged attention" and "Speculative decode".
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30
# K bytes one streamed block aims at: few enough blocks that the per-block
# work (softmax bookkeeping, waits) stays small, small enough that two
# slabs of K and V sit in VMEM with room to spare
_BLOCK_BYTES = 256 * 1024


def pages_per_block(page: int, kv_heads: int, head_dim: int, itemsize: int,
                    n_blocks: int, window: int = 0) -> int:
    """Pages per streamed KV block for a pool of these shapes: about
    ``_BLOCK_BYTES`` of K, at most ``n_blocks`` (the table width, or the
    pool's pages if fewer), and for windowed layers at most the window's
    pages."""
    ppb = max(1, _BLOCK_BYTES // (page * kv_heads * head_dim * itemsize))
    ppb = min(ppb, n_blocks)
    if window > 0:
        ppb = min(ppb, -(-window // page))
    return ppb


def pool_rows(shape) -> tuple:
    """The kernel's view ``(P, page, c, W)`` of a ``(P, page, KV, D)`` pool:
    each token is ``c`` rows of ``W`` lanes. Heads of a multiple of 128
    lanes get a row each (``c = KV``): the pool's layout holds them so, and
    the view is free. Narrower heads (granite's D = 64) would be padded to
    128 lanes in that layout, so a token's heads share one row (``c = 1``);
    writing such a pool through this view (``layers.attention_decode``)
    keeps XLA from relaying it out between the write and the kernel."""
    P, page, KV, D = shape
    c = KV if D % 128 == 0 else 1
    return P, page, c, KV * D // c


def _key_range(length, T: int, window: int):
    """Live key positions ``[lo, length)`` of a request: the keys any of
    its T query rows may see."""
    if window > 0:
        return jnp.maximum(length - T - window + 1, 0), length
    return 0, length


def _paged_kernel(bt_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf,
                  sem, carry_ref, m_ref, l_ref, acc_ref, *, page: int,
                  ppb: int, n_blocks: int, T: int, group: int, scale: float,
                  dequant: Optional[float], window: int):
    b = pl.program_id(0)
    n_req = pl.num_programs(0)
    bk = ppb * page                                # tokens per block
    c = k_hbm.shape[1] // page                     # slab rows per token
    n_rows = q_ref.shape[1]

    def block_range(r):
        lo, hi = _key_range(len_ref[r], T, window)
        return lo // bk, pl.cdiv(hi, bk)

    def page_copies(r, j, slot):
        # (live, K copy, V copy) per page of block j of request r: a page
        # is fetched only if it holds a key some query row may see
        lo, hi = _key_range(len_ref[r], T, window)
        out = []
        for i in range(ppb):
            p = j * ppb + i
            live = jnp.logical_and(p * page < hi, (p + 1) * page > lo)
            phys = bt_ref[r, jnp.minimum(p, n_blocks - 1)]
            out.append((live,
                        pltpu.make_async_copy(k_hbm.at[phys],
                                              kbuf.at[slot, i],
                                              sem.at[0, slot]),
                        pltpu.make_async_copy(v_hbm.at[phys],
                                              vbuf.at[slot, i],
                                              sem.at[1, slot])))
        return out

    def start(r, j, slot):
        for live, ck, cv in page_copies(r, j, slot):
            @pl.when(live)
            def _():
                ck.start()
                cv.start()

    def wait(r, j, slot):
        lo, hi = _key_range(len_ref[r], T, window)
        whole = jnp.logical_and(j * bk >= lo, (j + 1) * bk <= hi)

        @pl.when(whole)                            # every page was fetched:
        def _():                                   # one wait for the slab
            pltpu.make_async_copy(k_hbm.at[pl.ds(0, ppb)], kbuf.at[slot],
                                  sem.at[0, slot]).wait()
            pltpu.make_async_copy(v_hbm.at[pl.ds(0, ppb)], vbuf.at[slot],
                                  sem.at[1, slot]).wait()

        @pl.when(jnp.logical_not(whole))
        def _():
            for live, ck, cv in page_copies(r, j, slot):
                @pl.when(live)
                def _():
                    ck.wait()
                    cv.wait()

    @pl.when(b == 0)
    def _():
        carry_ref[0] = 0                           # first block in flight?
        carry_ref[1] = 0                           # ... in which slab

    j_lo, j_hi = block_range(b)
    nxt_lo, nxt_hi = block_range(jnp.minimum(b + 1, n_req - 1))
    has_next = jnp.logical_and(b + 1 < n_req, nxt_hi > nxt_lo)
    slot0 = carry_ref[1]

    @pl.when(jnp.logical_and(j_hi > j_lo, carry_ref[0] == 0))
    def _():
        start(b, j_lo, slot0)

    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    lo, hi = _key_range(length, T, window)
    base = length - T
    # query row r = h*T*G + t*G + g holds token t of KV head h; with c > 1
    # rows per token, head h's keys are slab row tok*c + h
    row = jax.lax.broadcasted_iota(jnp.int32, (n_rows, 1), 0)
    row_t = (row % (T * group)) // group
    col = jax.lax.broadcasted_iota(jnp.int32, (1, bk * c), 1)
    col_tok = col // c
    own_rows = (row // (T * group)) == col % c
    vtok = jax.lax.broadcasted_iota(jnp.int32, (bk * c, 1), 0) // c
    # bf16 q and K meet in bf16 with f32 accumulation: the products are
    # exact, as in f32; a wider side (f32, dequantized int8) widens both
    qk_dtype = jnp.promote_types(
        q_ref.dtype, jnp.float32 if dequant is not None else kbuf.dtype)
    q = q_ref[0].astype(qk_dtype)                  # (H*T, lanes)

    def body(j, slot):
        nxt = 1 - slot

        @pl.when(j + 1 < j_hi)
        def _():
            start(b, j + 1, nxt)

        @pl.when(jnp.logical_and(j + 1 == j_hi, has_next))
        def _():
            start(b + 1, nxt_lo, nxt)

        wait(b, j, slot)
        k = kbuf[slot].reshape(bk * c, -1)        # (bk*c, lanes)
        v = vbuf[slot].reshape(bk * c, -1)
        if dequant is not None:                    # int8 pool: slab dequant
            k = k.astype(jnp.float32) * dequant
            v = v.astype(jnp.float32) * dequant
        # q rows are zero outside their own head's lanes, so a row's score
        # against its own head's slab row is its attention score
        s = jax.lax.dot_general(
            q, k.astype(qk_dtype), dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (H*T, bk*c)
        pos = j * bk + col_tok
        # row t sees keys at positions < base + t + 1 (T == 1 reduces to
        # pos < length) and, with a window, > base + t - window
        mask = pos < base + row_t + 1
        if c > 1:
            mask = jnp.logical_and(mask, own_rows)
        if window > 0:
            mask = jnp.logical_and(mask, pos > base + row_t - window)
        s = jnp.where(mask, s, _NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
        # slab rows outside the live key range hold stale VMEM or never-
        # written tails: zero them, since p == 0 does not cancel a NaN
        vpos = j * bk + vtok
        v = jnp.where(jnp.logical_and(vpos >= lo, vpos < hi),
                      v.astype(jnp.float32), 0.0)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return nxt

    slot_end = jax.lax.fori_loop(j_lo, j_hi, body, slot0)
    carry_ref[0] = jnp.logical_and(j_hi > j_lo, has_next).astype(jnp.int32)
    carry_ref[1] = slot_end
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                ).astype(o_ref.dtype)


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    block_table: jax.Array, lengths, *,
                    kv_scale: Optional[float] = None, window: int = 0,
                    interpret: bool = True) -> jax.Array:
    """Flash-decode over a paged KV pool. Returns q's shape.

    q:           (B, H, D) — one new token per request — or (B, T, H, D),
                 a T-token query block per request (speculative verify;
                 post-rope, rows at absolute positions base .. base+T-1).
    k/v_pool:    (P, page, KV, D) shared page pools (bf16/f32 or int8).
    block_table: (B, n_blocks) int32 — logical block j of request b lives
                 in physical page ``block_table[b, j]`` (scratch page 0 for
                 never-written tails AND for window-recycled lead blocks;
                 masked out by ``lengths`` / ``window``).
    lengths:     (B,) int32 (or scalar) — live tokens per request
                 INCLUDING every token of the q block just written (i.e.
                 base + T). Traced. Row t attends causally to
                 ``lengths - T + t + 1`` keys.
    kv_scale:    static absmax bound when the pools are int8
                 (dequant = kv_scale / 127, matching layers.kv_dequant).
    window:      static sliding window (0 = full causal): row t sees only
                 keys at positions in ``(base + t - window, base + t]``.
                 Pages entirely below the window are not fetched — the
                 serving engine recycles them (their table entries are
                 scratch), so a windowed layer's sweep AND footprint stay
                 O(window) however long the request runs.
    """
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]                     # (B, H, D) -> (B, 1, H, D)
    B = q.shape[0]
    _, page, KV, D = k_pool.shape
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    ppb = pages_per_block(page, KV, D, k_pool.dtype.itemsize,
                          min(block_table.shape[1], k_pool.shape[0]), window)
    out = _paged(q, k_pool, v_pool, block_table, lengths,
                 kv_scale=kv_scale, window=window, ppb=ppb,
                 interpret=interpret)
    return out[:, 0] if squeeze else out


@functools.partial(jax.jit,
                   static_argnames=("kv_scale", "window", "ppb", "interpret"))
def _paged(q, k_pool, v_pool, block_table, lengths, *,
           kv_scale: Optional[float], window: int, ppb: int, interpret: bool
           ) -> jax.Array:
    B, T, H, D = q.shape
    P, page, KV, _ = k_pool.shape
    assert H % KV == 0, (H, KV)
    G = H // KV
    n_blocks = block_table.shape[1]
    dequant = None
    if jnp.issubdtype(k_pool.dtype, jnp.integer):
        assert kv_scale is not None, "int8 pools need kv_scale"
        dequant = kv_scale / 127.0

    # A page is page*c rows of W lanes (pool_rows); where c == 1 no score
    # is computed against another head's keys
    _, _, c, W = pool_rows(k_pool.shape)
    hpr = KV // c
    # (B, T, H, D) -> (B, KV*T*G, W): heads h*G..(h+1)*G-1 share kv head h
    # (matching layers._qkv head order) and the T query rows fold into the
    # group axis — row h*T*G + t*G + g — so the whole (token block x head
    # group) rides one streamed block. A row's D values sit at its head's
    # lanes of a slab row, zeros beside them.
    R = KV * T * G
    qg = (q.reshape(B, T, c, hpr, G, D).transpose(0, 2, 3, 1, 4, 5)
          .reshape(B, c, hpr, T * G, 1, D))
    own = jnp.eye(hpr, dtype=bool)[None, None, :, None, :, None]
    qg = jnp.where(own, qg, 0).reshape(B, R, W)
    k_rows = k_pool.reshape(P, page * c, W)
    v_rows = v_pool.reshape(P, page * c, W)
    if not interpret:       # left free, XLA may stage a whole pool in VMEM
        k_rows = pltpu.with_memory_space_constraint(k_rows, pltpu.HBM)
        v_rows = pltpu.with_memory_space_constraint(v_rows, pltpu.HBM)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # block_table, lengths
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, R, W), lambda b, bt, ln: (b, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.HBM),       # K pool
            pl.BlockSpec(memory_space=pltpu.HBM),       # V pool
        ],
        out_specs=pl.BlockSpec((1, R, W), lambda b, bt, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page * c, W), k_pool.dtype),   # K slabs
            pltpu.VMEM((2, ppb, page * c, W), v_pool.dtype),   # V slabs
            pltpu.SemaphoreType.DMA((2, 2)),           # (K/V, slab)
            pltpu.SMEM((2,), jnp.int32),   # next request's block in flight
            pltpu.VMEM((R, 1), jnp.float32),           # running max
            pltpu.VMEM((R, 1), jnp.float32),           # running denominator
            pltpu.VMEM((R, W), jnp.float32),           # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, page=page, ppb=ppb,
                          n_blocks=n_blocks, T=T, group=G, scale=D ** -0.5,
                          dequant=dequant, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, R, W), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # the grid must run in order: each request starts the next
            # one's first block
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_table, lengths, qg, k_rows, v_rows)
    # row r's output is its own head's D lanes of the accumulator
    out = jnp.diagonal(out.reshape(B, c, hpr, T, G, hpr, D), axis1=2, axis2=5)
    return out.transpose(0, 2, 1, 5, 3, 4).reshape(B, T, H, D)
