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
  prefetch** operands (``pltpu.PrefetchScalarGridSpec``), so the index map
  of the K/V pool can pick the physical page of grid step ``(b, h, i)``
  *before* the body runs — the pool is only ever touched one page at a
  time, straight from HBM into a VMEM tile;
* the grid walks ``(batch, logical_block)`` with the block axis
  innermost; one grid step streams one whole pool page ``(page, KV, D)``
  — the pool's contiguous unit, so the DMA is a single dense copy, never
  a strided per-head slice. Running max / denominator / output
  accumulator live in VMEM scratch across the page sweep (online
  softmax), so neither the gathered KV nor the score matrix ever exists
  outside a page-sized tile;
* GQA: all ``H = KV * G`` query heads ride the same streamed page (the
  chip's 3D-reuse argument applied to the KV stream) — the per-head
  score is a KV-batched ``(G, D) x (D, page)`` contraction;
* **multi-token query blocks** (speculative decode): ``q`` may carry
  ``T >= 1`` rows per request. The T axis is folded into the head-group
  axis — row ``r = t * G + g`` of a ``(KV, T*G, D)`` q tile — so the
  body stays the same KV-batched contraction while every row still rides
  the SAME streamed page (the verify step multiplies arithmetic
  intensity by T at unchanged page traffic). Causality is enforced
  in-sweep: query row ``t`` sits at absolute position ``base + t``
  (``base = lengths[b] - T``) and sees exactly ``base + t + 1`` keys;
* blocks past a request's valid length are skipped (``pl.when``), so a
  short request in a long-table batch pays for the pages it owns, not for
  ``max_blocks``;
* **sliding windows** (hybrid stacks, ``local_attn`` layers): a static
  ``window`` bounds how far back each query row may look. Grid steps
  whose page lies entirely below the earliest row's window start are
  skipped too — paired with the engine's page recycling
  (``runtime/kv_cache.release_prefix``) the sweep costs
  O(window / page) tiles per request however long its logical context
  grows — and the straddling page is trimmed by an extra in-sweep mask
  term (key position must exceed ``base + t - window``);
* int8 KV pools are dequantized tile-by-tile inside the kernel
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


def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                  acc_ref, *, page: int, n_blocks: int, n_rows: int,
                  group: int, scale: float, dequant: Optional[float],
                  window: int):
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    T = n_rows // group

    # skip pages entirely past this request's live tokens: the sweep costs
    # ceil(length/page) page tiles, not max_blocks (decode step >= 1 token,
    # so block 0 always runs in the full-causal case; windowed sweeps may
    # skip it, but the init/finalize pl.when blocks above/below run on
    # their grid steps regardless). With a window, pages entirely below
    # the EARLIEST query row's window start — key positions <=
    # base - window with base = length - T — are skipped as well: the
    # sweep touches O(window / page) live tiles however long the logical
    # context is (the engine recycles those pages; their table entries
    # point at scratch).
    run = i * page < length
    if window > 0:
        run = jnp.logical_and(run, (i + 1) * page > length - T - window + 1)

    @pl.when(run)
    def _block():
        q = q_ref[0].astype(jnp.float32)             # (KV, T*G, D)
        k = k_ref[0]                                 # (page, KV, D) — the
        v = v_ref[0]                                 # pool's contiguous unit
        if dequant is not None:                      # int8 pool: tile dequant
            k = k.astype(jnp.float32) * dequant
            v = v.astype(jnp.float32) * dequant
        # KV-batched (T*G, D) x (D, page) contraction: every query row of
        # the T-token block AND every head of the group scores against the
        # single page they all share
        s = jax.lax.dot_general(
            q, k.astype(jnp.float32),
            dimension_numbers=(((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32) * scale  # (KV, T*G, page)
        pos = i * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        # in-sweep causal mask: row r = t*G + g holds query token t, whose
        # absolute position is base + t with base = length - T; it may see
        # keys at positions < base + t + 1. T == 1 reduces to pos < length.
        # A sliding window additionally requires pos > base + t - window
        # (trims the straddling page; fully-dead pages were skipped above).
        t_row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) // group
        mask = pos < (length - T) + t_row + 1
        if window > 0:
            mask = jnp.logical_and(mask, pos > (length - T) + t_row - window)
        s = jnp.where(mask, s, _NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1))
        p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(-1)
        acc_ref[...] = (acc_ref[...] * corr[..., None]
                        + jax.lax.dot_general(
                            p, v.astype(jnp.float32),
                            dimension_numbers=(((2,), (0,)), ((0,), (1,))),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(i == n_blocks - 1)
    def _fin():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)[..., None]
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
                 Pages entirely below the window are skipped — the
                 serving engine recycles them (their table entries are
                 scratch), so a windowed layer's sweep AND footprint stay
                 O(window) however long the request runs.
    """
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]                     # (B, H, D) -> (B, 1, H, D)
    B = q.shape[0]
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    out = _paged(q, k_pool, v_pool, block_table, lengths,
                 kv_scale=kv_scale, window=window, interpret=interpret)
    return out[:, 0] if squeeze else out


@functools.partial(jax.jit,
                   static_argnames=("kv_scale", "window", "interpret"))
def _paged(q, k_pool, v_pool, block_table, lengths, *,
           kv_scale: Optional[float], window: int, interpret: bool
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

    # (B, T, H, D) -> (B, KV, T*G, D): heads h*G..(h+1)*G-1 share kv head h
    # (matching layers._qkv head order) and the T query rows fold into the
    # group axis — row r = t*G + g — so the whole (token block x head
    # group) rides one streamed page per grid step.
    qg = (q.reshape(B, T, KV, G, D).transpose(0, 2, 1, 3, 4)
          .reshape(B, KV, T * G, D))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # block_table, lengths
        grid=(B, n_blocks),
        in_specs=[
            pl.BlockSpec((1, KV, T * G, D), lambda b, i, bt, ln: (b, 0, 0, 0)),
            pl.BlockSpec((1, page, KV, D),
                         lambda b, i, bt, ln: (bt[b, i], 0, 0, 0)),
            pl.BlockSpec((1, page, KV, D),
                         lambda b, i, bt, ln: (bt[b, i], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, KV, T * G, D),
                               lambda b, i, bt, ln: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, T * G), jnp.float32),      # running max
            pltpu.VMEM((KV, T * G), jnp.float32),      # running denominator
            pltpu.VMEM((KV, T * G, D), jnp.float32),   # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, page=page, n_blocks=n_blocks,
                          n_rows=T * G, group=G, scale=D ** -0.5,
                          dequant=dequant, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, T * G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_table, lengths, qg, k_pool, v_pool)
    return (out.reshape(B, KV, T, G, D).transpose(0, 2, 1, 3, 4)
            .reshape(B, T, H, D))
