"""Layer library: norms, rotary, GQA attention (full/windowed/cross/decode),
chunked flash attention (pure-jnp online softmax), gated MLP, dropping MoE
and the dropless routed layer over the experts a chip holds.

All layers are functional: ``<layer>_init(mk, cfg, ...) -> params`` and
``<layer>_apply(cfg, params, ...) -> out``. ``mk`` is a ``Maker`` that either
initializes arrays or records logical sharding axes (same code path for both,
so the axes tree always matches the params tree).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.experts import row_tile
from repro.kernels.paged_attention import pool_rows
from repro.parallel.sharding import NO_RULES, Rules

# ---------------------------------------------------------------------------
# Param maker
# ---------------------------------------------------------------------------


class Maker:
    """mode='init' -> arrays; mode='axes' -> logical-axes strings (leaves)."""

    def __init__(self, mode: str, key=None, dtype=jnp.bfloat16):
        assert mode in ("init", "axes")
        self.mode = mode
        self.key = key
        self.dtype = dtype
        self._n = 0

    def __call__(self, shape, axes: str = "", scale: Optional[float] = None,
                 zeros: bool = False, ones: bool = False, dtype=None):
        if self.mode == "axes":
            return axes
        dt = dtype or self.dtype
        if ones:
            return jnp.ones(shape, dt)
        if zeros:
            return jnp.zeros(shape, dt)
        self._n += 1
        k = jax.random.fold_in(self.key, self._n)
        sc = scale if scale is not None else (shape[0] ** -0.5 if shape else 1.0)
        return (jax.random.normal(k, shape, jnp.float32) * sc).astype(dt)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def norm_init(mk: Maker, d: int, kind: str) -> Dict[str, Any]:
    p = {"scale": mk((d,), "", ones=True, dtype=jnp.float32)}
    if kind == "layernorm":
        p["bias"] = mk((d,), "", zeros=True, dtype=jnp.float32)
    return p


def norm_apply(p, x, kind: str, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if kind == "layernorm":
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:  # rmsnorm
        var = jnp.mean(jnp.square(xf), -1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary embedding
# ---------------------------------------------------------------------------


def rope(x, positions, theta: float):
    """x: (..., S, H, D); positions: (..., S) int32."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-jnp.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    ang = ang[..., None, :]                                  # (..., S, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Chunked flash attention (pure jnp; online softmax; bounded memory).
# The PDMA/VMEM-residency analogue at HLO level: per-(q,kv)-block partials
# only, never the full (S, S) score matrix.
# ---------------------------------------------------------------------------


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset=0, kv_valid: Optional[int] = None,
                    q_chunk: int = 256, kv_chunk: int = 512,
                    chunked: bool = True, scale: Optional[float] = None):
    """q: (B, Sq, H, D); k, v: (B, Sk, KV, D). GQA via head grouping.

    window > 0 -> sliding-window causal attention.
    kv_valid   -> only first `kv_valid` kv positions are real (static or traced).
    q_offset   -> absolute position of q[0] (scalar or (B,) traced).
    chunked=False -> one-shot softmax (no scan): the right path under
    sequence/context parallelism, where the per-device q block is already
    small — the chunk scan would otherwise materialize its (qc, kc)
    intermediates at every fusion boundary x trip count (the 36 TiB/step
    pathology of EXPERIMENTS.md §Perf A4).
    scale: the softmax scale (None = 1/sqrt(D)).
    """
    B, Sq, H, D = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    scale = D ** -0.5 if scale is None else scale
    if not chunked:
        qg = q.reshape(B, Sq, KV, G, D)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                       preferred_element_type=jnp.float32) * scale
        kpos = jnp.arange(Sk)
        kv_lim = Sk if kv_valid is None else kv_valid
        mask = kpos[None, :] < kv_lim
        if causal:
            qpos = jnp.arange(Sq) + (
                q_offset if jnp.ndim(q_offset) == 0 else q_offset[:, None])
            cm = qpos[..., :, None] >= kpos[None, :]
            if window:
                cm &= qpos[..., :, None] - kpos[None, :] < window
            mask = mask & cm
        if mask.ndim == 2:
            mask = mask[None]
        s = jnp.where(mask[:, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        return o.reshape(B, Sq, H, D).astype(q.dtype)
    qc, kc = min(q_chunk, Sq), min(kv_chunk, Sk)
    # pad to chunk multiples
    pq = (-Sq) % qc
    pk = (-Sk) % kc
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0))) if pq else q
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else v
    nq, nk = (Sq + pq) // qc, (Sk + pk) // kc
    qp = qp.reshape(B, nq, qc, KV, G, D)
    kp = kp.reshape(B, nk, kc, KV, D)
    vp = vp.reshape(B, nk, kc, KV, D)
    kv_lim = Sk if kv_valid is None else kv_valid

    def q_block(carry, qi):
        qb = qp[:, qi]  # (B, qc, KV, G, D)
        qpos = qi * qc + jnp.arange(qc) + (
            q_offset if jnp.ndim(q_offset) == 0 else q_offset[:, None])

        def kv_block(acc, ki):
            m, l, o = acc
            kb, vb = kp[:, ki], vp[:, ki]
            kpos = ki * kc + jnp.arange(kc)
            s = jnp.einsum("bqkgd,bckd->bkgqc", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            mask = (kpos[None, :] < kv_lim)
            if causal:
                cm = qpos[..., :, None] >= kpos[None, :]
                if window:
                    cm &= qpos[..., :, None] - kpos[None, :] < window
                mask = mask & cm
            # mask: (qc, kc) or (B, qc, kc) -> broadcast over (b, k, g, q, c)
            if mask.ndim == 2:
                mask = mask[None]
            s = jnp.where(mask[:, None, None], s, -1e30)
            m2 = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m2[..., None])
            corr = jnp.exp(m - m2)
            l2 = l * corr + p.sum(-1)
            o2 = o * corr[..., None] + jnp.einsum(
                "bkgqc,bckd->bkgqd", p.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32)
            return (m2, l2, o2), None

        init = (jnp.full((B, KV, G, qc), -jnp.inf, jnp.float32),
                jnp.zeros((B, KV, G, qc), jnp.float32),
                jnp.zeros((B, KV, G, qc, D), jnp.float32))
        (m, l, o), _ = jax.lax.scan(kv_block, init, jnp.arange(nk))
        o = o / jnp.maximum(l[..., None], 1e-30)
        return carry, o.transpose(0, 3, 1, 2, 4)  # (B, qc, KV, G, D)

    _, outs = jax.lax.scan(q_block, None, jnp.arange(nq))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * qc, H, D)
    return out[:, :Sq].astype(q.dtype)


def attend_decode(q, ck, cv, pos, *, window: int = 0, ring: bool = False,
                  kv_chunk: int = 0, scale: Optional[float] = None):
    """Decode attention vs a cache. q: (B, Tq, H, D) — Tq == 1 for plain
    decode, Tq > 1 for a speculative multi-token query block; ck/cv:
    (B, S, KV, D); pos: (B,) absolute position of the FIRST new token
    (row t sits at pos + t; the cache holds every earlier token plus the
    block itself, so row t attends to pos + t + 1 keys — in-block causal).

    window > 0 bounds each row to the last ``window`` keys. Two layouts:

    * ``ring=False`` (paged / contiguous): buffer index == absolute key
      position, so the window is a per-row position band
      ``(pos + t - window, pos + t]`` — works for any Tq (the paged
      sliding-window oracle, matching ops.paged_attention's masking).
    * ``ring=True``: ``ck``/``cv`` is a window-sized ring buffer (slot =
      position % S) that only ever HOLDS the last S positions, so
      masking is by valid-slot count. Single-token by construction: a
      Tq > 1 block's older rows would need positions the ring has
      already overwritten — rejected with a ValueError (surfaced with
      the layer kind at api.decode_step; see ISSUE 5 satellite).

    Chunked over the cache length with an online softmax so the (B, KV, G,
    Tq, S) score tensor is never materialized — for a 32k cache this is
    the difference between streaming the cache once and ~6 fp32 passes
    over a 17 GB intermediate (EXPERIMENTS.md §Perf C3). ``scale`` is the
    softmax scale (None = 1/sqrt(D))."""
    B, Tq, H, D = q.shape
    scale = D ** -0.5 if scale is None else scale
    _, S, KV, _ = ck.shape
    G = H // KV
    qg = q.reshape(B, Tq, KV, G, D)
    if ring:
        if Tq != 1:
            raise ValueError(
                f"ring-buffer windowed decode is single-token (got a "
                f"Tq={Tq} query block): the ring has already overwritten "
                f"positions the block's older rows would attend to — use "
                f"the paged window layout (ring=False) for multi-token "
                f"blocks")
        nvalid = jnp.minimum(pos + 1, S)[:, None]  # ring buffer: slot count
    else:
        nvalid = pos[:, None] + jnp.arange(Tq)[None, :] + 1    # (B, Tq)
    c = S if kv_chunk <= 0 else min(kv_chunk, S)
    if S % c:
        c = S  # ragged cache lengths: single chunk (small-cache tests)
    nc = S // c
    ckc = ck.reshape(B, nc, c, KV, D)
    cvc = cv.reshape(B, nc, c, KV, D)

    def chunk(acc, i):
        m, l, o = acc
        kb = ckc[:, i]
        vb = cvc[:, i]
        s = jnp.einsum("btkgd,bckd->bkgtc", qg, kb,
                       preferred_element_type=jnp.float32) * scale
        slots = i * c + jnp.arange(c)
        mask = slots[None, None, :] < nvalid[:, :, None]       # (B, Tq, c)
        if window and not ring:
            # buffer index == absolute position: drop keys older than the
            # row's window (the ring layout never holds them to begin with)
            mask = mask & (slots[None, None, :]
                           > nvalid[:, :, None] - 1 - window)
        mask = mask[:, None, None]                  # over (b, k, g, t, c)
        m2 = jnp.maximum(m, jnp.where(mask, s, -jnp.inf).max(-1))
        m2 = jnp.maximum(m2, -1e30)       # fully-masked chunk guard
        p = jnp.where(mask, jnp.exp(s - m2[..., None]), 0.0)
        corr = jnp.exp(m - m2)
        l2 = l * corr + p.sum(-1)
        o2 = o * corr[..., None] + jnp.einsum(
            "bkgtc,bckd->bkgtd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m2, l2, o2), None

    init = (jnp.full((B, KV, G, Tq), -1e30, jnp.float32),
            jnp.zeros((B, KV, G, Tq), jnp.float32),
            jnp.zeros((B, KV, G, Tq, D), jnp.float32))
    if nc == 1:
        (m, l, o), _ = chunk(init, 0)
    else:
        (m, l, o), _ = jax.lax.scan(chunk, init, jnp.arange(nc))
    o = o / jnp.maximum(l[..., None], 1e-30)
    return o.transpose(0, 3, 1, 2, 4).reshape(B, Tq, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# KV-cache quantization (opt-in int8 storage; the chip's INT8 theme applied
# to the decode cache — halves cache footprint and read traffic)
# ---------------------------------------------------------------------------


def kv_quant(cfg, x):
    """bf16 k/v -> cache storage dtype (symmetric, static absmax bound)."""
    if cfg.kv_cache_dtype != "int8":
        return x.astype(jnp.dtype(cfg.kv_cache_dtype))
    q = jnp.round(x.astype(jnp.float32) * (127.0 / cfg.kv_scale))
    return jnp.clip(q, -127, 127).astype(jnp.int8)


def kv_dequant(cfg, x, dtype):
    if x.dtype != jnp.int8:
        return x.astype(dtype)
    return (x.astype(jnp.float32) * (cfg.kv_scale / 127.0)).astype(dtype)


# ---------------------------------------------------------------------------
# Attention layer (GQA, optional bias / window / cross)
# ---------------------------------------------------------------------------


def attention_init(mk: Maker, cfg) -> Dict[str, Any]:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.kv_heads, cfg.resolved_head_dim
    p = {
        "wq": mk((d, h, hd), "wembed,wheads", scale=d ** -0.5),
        "wk": mk((d, kv, hd), "wembed,wkv_heads", scale=d ** -0.5),
        "wv": mk((d, kv, hd), "wembed,wkv_heads", scale=d ** -0.5),
        "wo": mk((h, hd, d), "wheads,,wembed", scale=(h * hd) ** -0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = mk((h, hd), "wheads", zeros=True)
        p["bk"] = mk((kv, hd), "wkv_heads", zeros=True)
        p["bv"] = mk((kv, hd), "wkv_heads", zeros=True)
    return p


def _scale(cfg) -> Optional[float]:
    """The softmax scale: the configured attention multiplier, else None
    (1/sqrt(head_dim))."""
    return cfg.attention_multiplier or None


def _position(cfg, q, k, positions):
    """Rotary q and k at ``positions``; as they are under "nope"."""
    if cfg.position_embedding == "nope":
        return q, k
    return rope(q, positions, cfg.rope_theta), rope(k, positions,
                                                    cfg.rope_theta)


def _qkv(cfg, p, x, kv_x=None):
    kv_x = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    k = jnp.einsum("bsd,dhe->bshe", kv_x, p["wk"])
    v = jnp.einsum("bsd,dhe->bshe", kv_x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return q, k, v


def attention_apply(cfg, p, x, *, rules: Rules = NO_RULES, positions=None,
                    window: int = 0, cross_kv=None, causal: bool = True,
                    prefix=None):
    """Full-sequence attention (train/prefill). Returns (out, kv) so callers
    can build caches. cross_kv=(k,v) for encoder-decoder cross attention.

    prefix=(pk, pv, plen): suffix-only prefill against a reused KV prefix
    (prefix sharing, runtime/prefix_cache.py). ``x`` holds only the tokens
    AFTER the shared prefix; pk/pv (B, Pb, KV, D, cache storage dtype) are
    the prefix KV gathered from the paged pool, valid for the first
    ``plen`` (traced) rows; ``positions`` must already be offset by plen.
    The suffix k/v are spliced in at row plen, so buffer index == absolute
    position for every real token and plain causal masking handles both
    the prefix-buffer tail and the suffix bucket padding (garbage rows sit
    at positions > every real query). Returned kv is the SUFFIX-only k/v —
    exactly what the caller must scatter into its pages."""
    B, S, _ = x.shape
    if cross_kv is not None:
        q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
        if cfg.qkv_bias:
            q = q + p["bq"]
        k, v = cross_kv
        q = rules.cons(q, "batch,seq,heads")
        out = flash_attention(q, k, v, causal=False, scale=_scale(cfg))
        kv = None
    else:
        q, k, v = _qkv(cfg, p, x)
        if positions is None:
            positions = jnp.arange(S)[None, :]
        q, k = _position(cfg, q, k, positions)
        q = rules.cons(q, "batch,seq,heads")
        k = rules.cons(k, "batch,seq,kv_heads")
        if prefix is not None:
            pk, pv, plen = prefix
            plen = jnp.asarray(plen, jnp.int32)
            start = (jnp.int32(0), plen, jnp.int32(0), jnp.int32(0))
            kf = jnp.concatenate([kv_dequant(cfg, pk, x.dtype),
                                  jnp.zeros_like(k)], axis=1)
            vf = jnp.concatenate([kv_dequant(cfg, pv, x.dtype),
                                  jnp.zeros_like(v)], axis=1)
            kf = jax.lax.dynamic_update_slice(kf, k, start)
            vf = jax.lax.dynamic_update_slice(vf, v, start)
            out = flash_attention(q, kf, vf, causal=causal, window=window,
                                  q_offset=plen,
                                  chunked=cfg.flash_chunking,
                                  scale=_scale(cfg))
        else:
            out = flash_attention(q, k, v, causal=causal, window=window,
                                  chunked=cfg.flash_chunking,
                                  scale=_scale(cfg))
        kv = (k, v)
    out = jnp.einsum("bshe,hed->bsd", out, p["wo"])
    # contraction over heads: under manual TP (ManualRules inside a
    # shard_map body) each shard holds H/M heads and this is the psum
    # point; identity everywhere else
    out = rules.contract(out, "heads")
    return rules.cons(out, "batch,seq,embed"), kv


def attention_decode(cfg, p, x, cache, pos, *, rules: Rules = NO_RULES,
                     window: int = 0, cross: bool = False,
                     block_table=None):
    """Decode step. x: (B, T, d) — T == 1 for plain decode; pos: (B,)
    position of the FIRST new token. Returns (out, new_cache).

    Dense mode (block_table=None): cache {"k","v"}: (B, S, KV, D), one lane
    per batch slot; single-token only (T == 1). window > 0 means the lane
    is a window-sized ring buffer (slot = pos % S).
    Paged mode: cache {"k","v"}: (P, page, KV, D) — a shared page pool —
    and block_table: (B, n_blocks) int32 mapping each request's logical
    blocks to physical pages (repro.runtime.kv_cache). The T new tokens
    are scattered token-granularly into their owner's pages (a block may
    straddle a page boundary; rows past the table's capacity land on the
    scratch page — their logits are only ever produced to be discarded by
    the engine's max_len stop); attention then runs the block-table
    indirection INSIDE the flash-decode kernel (ops.paged_attention), one
    multi-page KV block at a time, causally masked row-by-row against
    pos + T — so pool garbage (scratch page, not-yet-written tail) never
    contributes probability mass and the dense (B, n_blocks*page, KV, D)
    gathered KV never materializes. T > 1 is the speculative-verify block (engine
    spec_k): K drafted tokens + the current one score in ONE page sweep.
    window > 0 in paged mode is a sliding-window layer (hybrid
    local_attn) on the paged layout: logical block index still means
    absolute position, the kernel masks each row to its last `window`
    keys and skips pages entirely below the window — the ones the engine
    recycles to scratch (runtime/kv_cache.release_prefix) — so the layer
    holds O(window) live pages however long the request runs.
    cfg.paged_attn_impl == "gather" keeps the PR-1 dense-gather path as
    the measured baseline (benchmarks/serve_bench.py)."""
    if cross:
        q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
        if cfg.qkv_bias:
            q = q + p["bq"]
        ck = kv_dequant(cfg, cache["k"], x.dtype)
        cv = kv_dequant(cfg, cache["v"], x.dtype)
        n = jnp.full((x.shape[0],), ck.shape[1], jnp.int32)
        out = attend_decode(q, ck, cv, n - 1, scale=_scale(cfg))
        new_cache = cache
    elif block_table is not None:
        B, T = x.shape[0], x.shape[1]
        q, k, v = _qkv(cfg, p, x)
        pos_t = pos[:, None] + jnp.arange(T)[None, :]        # (B, T)
        q, k = _position(cfg, q, k, pos_t)
        page = cache["k"].shape[1]
        n_blk = block_table.shape[1]
        # physical destination of row t: page block_table[b, (pos+t)//page],
        # row (pos+t)%page — token-granular, so a T-block may straddle a
        # page boundary. Dead slots carry an all-scratch table, and rows
        # past the table's capacity (a verify block overrunning max_len —
        # their logits are discarded by the engine's max_len stop) are
        # redirected to the scratch page (id 0) too, so neither can ever
        # scribble over a live lane.
        blk = pos_t // page
        phys = jnp.where(
            blk < n_blk,
            jnp.take_along_axis(block_table,
                                jnp.minimum(blk, n_blk - 1), axis=1),
            0)
        off = pos_t % page
        # the kernel reads the pool through its row view: write through
        # the same view, so XLA keeps one layout between the two
        rows = (cache["k"].shape if cfg.paged_attn_impl == "gather"
                else pool_rows(cache["k"].shape))

        def write(pool, x):
            return pool.reshape(rows).at[phys, off].set(
                kv_quant(cfg, x).reshape(B, T, *rows[2:])).reshape(pool.shape)

        ck, cv = write(cache["k"], k), write(cache["v"], v)
        if cfg.paged_attn_impl == "gather":
            # PR-1 baseline: dense per-layer pool gather (the "separated
            # memory" anti-pattern; kept only for serve_bench comparison).
            # Windowed layers mask by absolute position band (ring=False:
            # buffer index == absolute position in this layout).
            kg = ck[block_table].reshape(B, n_blk * page, *ck.shape[2:])
            vg = cv[block_table].reshape(B, n_blk * page, *cv.shape[2:])
            out = attend_decode(q, kv_dequant(cfg, kg, q.dtype),
                                kv_dequant(cfg, vg, q.dtype), pos,
                                window=window,
                                kv_chunk=cfg.decode_kv_chunk,
                                scale=_scale(cfg))
        else:
            scale = cfg.kv_scale if ck.dtype == jnp.int8 else None
            out = ops.paged_attention(q, ck, cv, block_table,
                                      pos + T, kv_scale=scale,
                                      window=window, scale=_scale(cfg))
        new_cache = {"k": ck, "v": cv}
    else:
        q, k, v = _qkv(cfg, p, x)
        q, k = _position(cfg, q, k, pos[:, None])
        S = cache["k"].shape[1]
        slot = jnp.remainder(pos, S) if window else jnp.minimum(pos, S - 1)
        # one-hot masked write instead of a per-batch dynamic-update-slice:
        # elementwise over the cache, so it partitions cleanly when the
        # cache seq axis is context-parallel sharded (a vmapped DUS at a
        # traced index forces SPMD to re-materialize the whole cache —
        # EXPERIMENTS.md §Perf C3).
        hit = (jnp.arange(S)[None, :] == slot[:, None])[..., None, None]
        ck = jnp.where(hit, kv_quant(cfg, k), cache["k"])
        cv = jnp.where(hit, kv_quant(cfg, v), cache["v"])
        ck = rules.cons(ck, "batch,seq,kv_heads")
        cv = rules.cons(cv, "batch,seq,kv_heads")
        out = attend_decode(q, kv_dequant(cfg, ck, q.dtype),
                            kv_dequant(cfg, cv, q.dtype), pos,
                            window=window, ring=window > 0,
                            kv_chunk=cfg.decode_kv_chunk, scale=_scale(cfg))
        new_cache = {"k": ck, "v": cv}
    out = jnp.einsum("bshe,hed->bsd", out, p["wo"])
    out = rules.contract(out, "heads")   # TP psum point (see attention_apply)
    return rules.cons(out, "batch,seq,embed"), new_cache


# ---------------------------------------------------------------------------
# MLP (gated or plain)
# ---------------------------------------------------------------------------


def mlp_init(mk: Maker, cfg, d_ff: Optional[int] = None) -> Dict[str, Any]:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    p = {"wi": mk((d, f), "wembed,wff", scale=d ** -0.5),
         "wo": mk((f, d), "wff,wembed", scale=f ** -0.5)}
    if cfg.gated_ffn:
        p["wg"] = mk((d, f), "wembed,wff", scale=d ** -0.5)
    return p


def _act(cfg, h):
    return jax.nn.silu(h) if cfg.act == "silu" else jax.nn.gelu(h)


def mlp_apply(cfg, p, x, *, rules: Rules = NO_RULES):
    h = jnp.einsum("bsd,df->bsf", x, p["wi"])
    if cfg.gated_ffn:
        h = _act(cfg, jnp.einsum("bsd,df->bsf", x, p["wg"])) * h
    else:
        h = _act(cfg, h)
    h = rules.cons(h, "batch,seq,ffn")
    out = jnp.einsum("bsf,fd->bsd", h, p["wo"])
    # contraction over ffn: the TP psum point when wi/wg/wo are sharded
    # over the model axis (identity otherwise — including MoE configs,
    # whose plan never shards ffn so the shared expert stays correct)
    out = rules.contract(out, "ffn")
    return rules.cons(out, "batch,seq,embed")


# ---------------------------------------------------------------------------
# Dropping MoE (capacity factor; cumsum position assignment; EP over experts)
# ---------------------------------------------------------------------------


def moe_init(mk: Maker, cfg) -> Dict[str, Any]:
    """The router scores every expert; the expert stacks hold every expert
    for the dropping layer and the experts held here for the dropless one
    (``routed_apply``)."""
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe.num_experts
    held = cfg.held_experts[1] if is_dropless(cfg) else e
    p = {
        "router": mk((d, e), "wembed,wexperts", scale=d ** -0.5, dtype=jnp.float32),
        "wi": mk((held, d, f), "wexperts,wembed,wff", scale=d ** -0.5),
        "wg": mk((held, d, f), "wexperts,wembed,wff", scale=d ** -0.5),
        "wo": mk((held, f, d), "wexperts,wff,wembed", scale=f ** -0.5),
    }
    if cfg.moe.shared_expert:
        p["shared"] = mlp_init(mk, cfg, cfg.moe.shared_d_ff or None)
    return p


def is_dropless(cfg) -> bool:
    return cfg.moe is not None and cfg.moe.capacity_factor <= 0


def moe_apply(cfg, p, x, *, rules: Rules = NO_RULES):
    """Token-dropping MoE with GShard-style grouped dispatch.

    Tokens are split into `dispatch_groups` groups; capacity is enforced
    per group and the group dim carries the batch sharding, so the
    routing scatter/gather stay local to their data shard while the
    expert dim is tensor-parallel. Without grouping, SPMD replicates the
    global-capacity buffer and all-reduces it every layer, and every data
    rank runs the full expert GEMM (EXPERIMENTS.md §Perf B3/B5/B6).
    Returns (out, aux_losses)."""
    B, S, d = x.shape
    m = cfg.moe
    E, K = m.num_experts, m.top_k
    T = B * S
    G = m.dispatch_groups if T % max(m.dispatch_groups, 1) == 0 else 1
    Tg = T // G
    xt = x.reshape(T, d)
    logits = (xt.astype(jnp.float32) @ p["router"]).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, -1)
    gate_w, gate_i = jax.lax.top_k(probs, K)                              # (T, K)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    C = int(max(4, -(-Tg * m.capacity_factor * K) // E))   # per group
    # priority order within each group: slot-major, matching Switch.
    eidx = gate_i.reshape(G, Tg, K).transpose(0, 2, 1).reshape(G, K * Tg)
    oh = jax.nn.one_hot(eidx, E, dtype=jnp.int32)          # (G, K*Tg, E)
    pos = jnp.cumsum(oh, 1) - 1
    pos = jnp.take_along_axis(pos, eidx[..., None], 2)[..., 0]
    keep = pos < C
    flat = jnp.where(keep, eidx * C + pos, E * C)          # (G, K*Tg)

    xg = xt.reshape(G, Tg, d)
    xrep = (jnp.broadcast_to(xg[:, None], (G, K, Tg, d))
            .reshape(G, K * Tg, d))
    xrep = rules.cons(xrep, "batch,,embed")

    def scatter(fl, xr):
        return jnp.zeros((E * C + 1, d), x.dtype).at[fl].add(xr)

    buf = jax.vmap(scatter)(flat, xrep)                    # (G, E*C+1, d)
    buf = rules.cons(buf[:, : E * C].reshape(G, E, C, d), "batch,experts")

    h = jnp.einsum("gecd,edf->gecf", buf, p["wi"])
    g = jnp.einsum("gecd,edf->gecf", buf, p["wg"])
    h = rules.cons(jax.nn.silu(g) * h, "batch,experts,,ffn")
    eo = jnp.einsum("gecf,efd->gecd", h, p["wo"])
    eo = rules.cons(eo, "batch,experts")

    eo_flat = jnp.concatenate(
        [eo.reshape(G, E * C, d), jnp.zeros((G, 1, d), eo.dtype)], 1)
    got = jnp.take_along_axis(eo_flat, flat[..., None], 1)  # (G, K*Tg, d)
    got = rules.cons(got, "batch,,embed").reshape(G, K, Tg, d)
    w = (gate_w.reshape(G, Tg, K).transpose(0, 2, 1)
         * keep.reshape(G, K, Tg)).astype(x.dtype)
    out = jnp.einsum("gkt,gktd->gtd", w, got).reshape(B, S, d)
    if m.shared_expert:
        out = out + mlp_apply(cfg, p["shared"], x, rules=rules)

    # aux losses: load-balance (Switch) + router z-loss (global)
    me = probs.mean(0)                                   # (E,)
    ce = jnp.zeros((E,)).at[gate_i.reshape(-1)].add(1.0) / (T * K)
    lb = E * jnp.sum(me * ce)
    z = jnp.mean(jax.scipy.special.logsumexp(logits, -1) ** 2)
    return rules.cons(out, "batch,seq,embed"), {"lb_loss": lb, "z_loss": z}


# ---------------------------------------------------------------------------
# Dropless routed layer over the experts this chip holds
# ---------------------------------------------------------------------------

# tokens routed in one grouped call; a longer prefill runs its tokens
# through the experts this many at a time, so the routed rows of one
# call (tokens x top_k x d_model) stay a few hundred MB at most
ROUTED_CHUNK = 2048


def routed_apply(cfg, p, x, *, rules: Rules = NO_RULES):
    """Dropless routed FFN. x: (B, S, d) -> (out, rows): ``rows`` (held,)
    int32 counts the routed rows each held expert took.

    The router scores all ``num_experts`` in float32; each token takes its
    ``top_k`` best and weights them by the softmax over those logits (as
    Granite's and Mixtral's routers do). Rows routed to experts this chip
    holds (``cfg.held_experts``) run through their expert's SwiGLU in one
    grouped call over the held experts, sorted by expert
    (``kernels/experts.py``); rows routed to the other experts add
    nothing here. No token is dropped, so a request's output does not
    depend on the batch it shares. The shared expert, of its own width,
    adds to every token."""
    B, S, d = x.shape
    first, held = cfg.held_experts
    T = B * S
    xt = x.reshape(T, d)
    logits = xt.astype(jnp.float32) @ p["router"]             # (T, E)
    top, idx = jax.lax.top_k(logits, cfg.moe.top_k)
    gate = jax.nn.softmax(top, -1)
    experts = (p["wg"], p["wi"], p["wo"])
    # chunks in sequence, unrolled: a loop would carry the expert weights
    # as loop state, a copy of them per layer
    parts = [_routed_rows(xt[i:i + ROUTED_CHUNK], idx[i:i + ROUTED_CHUNK]
                          - first, gate[i:i + ROUTED_CHUNK], experts, held)
             for i in range(0, T, ROUTED_CHUNK)]
    out = jnp.concatenate([o for o, _ in parts]).reshape(B, S, d)
    rows = sum(r for _, r in parts)
    if cfg.moe.shared_expert:
        out = out + mlp_apply(cfg, p["shared"], x, rules=rules)
    return rules.cons(out, "batch,seq,embed"), rows


def _routed_rows(xt, local, gate, experts, held: int):
    """Tokens ``xt`` (T, d) routed to ``local`` (T, K) expert ids (this
    chip's numbering: held experts are 0 .. held - 1) with weights
    ``gate`` (T, K) -> ((T, d), rows per held expert)."""
    T, K = local.shape
    flat = local.reshape(-1)
    mine = (flat >= 0) & (flat < held)
    key = jnp.where(mine, flat, held)
    order = jnp.argsort(key, stable=True)           # held experts first
    rows = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    m = T * K
    pad = (-m) % row_tile(m)
    src = jnp.take(xt, order // K, axis=0)
    if pad:
        src = jnp.pad(src, ((0, pad), (0, 0)))
    y = ops.swiglu_experts(src, *experts, rows)[:m]
    back = jnp.zeros_like(order).at[order].set(jnp.arange(m))
    y = jnp.take(y, back, axis=0).reshape(T, K, -1)
    w = jnp.where(mine, gate.reshape(-1), 0.0).reshape(T, K)
    out = jnp.einsum("tk,tkd->td", w.astype(y.dtype), y,
                     preferred_element_type=jnp.float32)
    return out.astype(xt.dtype), rows
