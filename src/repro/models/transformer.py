"""Composable decoder (+ optional encoder) built from pattern blocks.

A model is ``embedding -> scan over super-blocks -> tail blocks -> norm ->
head``. A *super-block* is one period of the arch's block pattern (e.g.
RecurrentGemma: ``(rglru, rglru, local_attn)``); homogeneous params of each
pattern position are stacked and scanned (small HLO, production-style).
Layers that don't fit a whole period form an explicitly-applied tail.

Block kinds: ``attn_mlp`` / ``attn_moe`` (attention, then a dense or a
routed FFN), ``local_attn`` (sliding window + dense FFN), ``rglru``
(RG-LRU + dense FFN), ``ssm`` (a bare Mamba-2 mixer), ``ssm_moe``
(Mamba-2 mixer, then a routed FFN: Granite 4.0-H), ``enc`` / ``dec``.
A routed FFN is the dropping ``moe_apply`` where the MoE config has a
capacity, else the dropless ``routed_apply`` over the experts held here.
Every branch is scaled by ``cfg.residual_multiplier`` before its
residual add.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import griffin, ssm
from repro.models.layers import (Maker, attention_apply, attention_decode,
                                 attention_init, is_dropless, mlp_apply,
                                 mlp_init, moe_apply, moe_init, norm_apply,
                                 norm_init, routed_apply)
from repro.parallel.sharding import NO_RULES, Rules

VOCAB_PAD = 256


def padded_vocab(v: int) -> int:
    return -(-v // VOCAB_PAD) * VOCAB_PAD


# ---------------------------------------------------------------------------
# Block pattern
# ---------------------------------------------------------------------------


def pattern_for(cfg) -> Tuple[str, ...]:
    if cfg.is_encdec:
        return ("dec",)
    if cfg.family == "ssm":
        return ("ssm",)
    if cfg.family == "hybrid":
        return cfg.hybrid.pattern
    if cfg.moe is not None and cfg.moe.num_experts > 0:
        period = cfg.moe.moe_every
        return tuple(["attn_mlp"] * (period - 1) + ["attn_moe"])
    return ("attn_mlp",)


def layer_plan(cfg) -> Tuple[int, Tuple[str, ...]]:
    """(n_super, tail_kinds)."""
    pat = pattern_for(cfg)
    p = len(pat)
    return cfg.num_layers // p, pat[: cfg.num_layers % p]


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def block_init(mk: Maker, cfg, kind: str) -> Dict[str, Any]:
    d = cfg.d_model
    p: Dict[str, Any] = {"ln1": norm_init(mk, d, cfg.norm)}
    if kind in ("attn_mlp", "attn_moe", "local_attn", "enc", "dec"):
        p["attn"] = attention_init(mk, cfg)
    elif kind == "ssm":
        p["mixer"] = ssm.ssm_init(mk, cfg)
        return p  # pure mamba block: no FFN sublayer
    elif kind == "ssm_moe":
        p["mixer"] = ssm.ssm_init(mk, cfg)
    elif kind == "rglru":
        p["mixer"] = griffin.rglru_init(mk, cfg)
    else:
        raise ValueError(kind)
    if kind == "dec":
        p["lnx"] = norm_init(mk, d, cfg.norm)
        p["cross"] = attention_init(mk, cfg)
    p["ln2"] = norm_init(mk, d, cfg.norm)
    if kind in ("attn_moe", "ssm_moe"):
        p["moe"] = moe_init(mk, cfg)
    else:
        p["mlp"] = mlp_init(mk, cfg)
    return p


def _norm(cfg, p, x):
    return norm_apply(p, x, cfg.norm, cfg.norm_eps)


def _res(cfg, x, a):
    """The residual add of a branch, scaled by the residual multiplier."""
    if cfg.residual_multiplier != 1.0:
        a = a * cfg.residual_multiplier
    return x + a


def _ffn(cfg, kind, p, x, rules):
    """The FFN sublayer of a block (norm, FFN, residual add) -> (x, aux,
    rows): ``rows`` counts the routed rows of each held expert for the
    dropless routed layer, else None."""
    h2 = _norm(cfg, p["ln2"], x)
    aux, rows = {}, None
    if kind not in ("attn_moe", "ssm_moe"):
        f = mlp_apply(cfg, p["mlp"], h2, rules=rules)
    elif is_dropless(cfg):
        f, rows = routed_apply(cfg, p["moe"], h2, rules=rules)
    else:
        f, aux = moe_apply(cfg, p["moe"], h2, rules=rules)
    return _res(cfg, x, f), aux, rows


def _grow(cfg, kv, max_len):
    """Pad a full-attention prefill kv (B, S, KV, D) out to max_len slots
    (stored in the cache dtype — int8 when kv_cache_dtype says so)."""
    from repro.models.layers import kv_quant
    k, v = kv_quant(cfg, kv[0]), kv_quant(cfg, kv[1])
    pad = max(0, (max_len or 0) - k.shape[1])
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return {"k": k, "v": v}


def block_apply(cfg, kind: str, p, x, *, rules: Rules = NO_RULES,
                positions=None, enc_out=None, want_cache: bool = False,
                max_len=None, prefix=None, length=None,
                paged_kv: bool = False):
    """Full-sequence block. Returns (x, cache_entry, aux). prefix=(pk, pv,
    plen) switches full attention to suffix-only prefill against reused
    prefix KV (layers.attention_apply); the cache entry then holds the
    suffix k/v only.

    length (scalar/(B,), may be traced): REAL token count when x is
    right-padded to a bucket (paged bucketed prefill) — recurrent blocks
    mask their state updates past it so the returned state is the state
    at length - 1 (ssm/rglru_apply). paged_kv=True makes local_attn
    return the FULL-sequence kv like full attention (the paged engine
    scatters only the live window blocks into pages) instead of the
    dense engine's window-sized ring buffer."""
    cache = None
    h = _norm(cfg, p["ln1"], x)
    if kind in ("attn_mlp", "attn_moe", "dec"):
        a, kv = attention_apply(cfg, p["attn"], h, rules=rules,
                                positions=positions, prefix=prefix)
        if want_cache:
            cache = _grow(cfg, kv, max_len)
    elif kind == "local_attn":
        w = cfg.hybrid.window
        a, kv = attention_apply(cfg, p["attn"], h, rules=rules,
                                positions=positions, window=w)
        if want_cache:
            cache = _grow(cfg, kv, None) if paged_kv \
                else _window_cache(cfg, kv, w)
    elif kind == "enc":
        a, _ = attention_apply(cfg, p["attn"], h, rules=rules,
                               positions=positions, causal=False)
    elif kind in ("ssm", "ssm_moe"):
        if want_cache:
            a, cache = ssm.ssm_apply(cfg, p["mixer"], h, rules=rules,
                                     return_state=True, length=length)
        else:
            a = ssm.ssm_apply(cfg, p["mixer"], h, rules=rules)
        if kind == "ssm":
            return _res(cfg, x, a), cache, {}
    elif kind == "rglru":
        if want_cache:
            a, cache = griffin.rglru_apply(cfg, p["mixer"], h, rules=rules,
                                           return_state=True, length=length)
        else:
            a = griffin.rglru_apply(cfg, p["mixer"], h, rules=rules)
    else:
        raise ValueError(kind)
    x = _res(cfg, x, a)
    if kind == "dec":
        hx = _norm(cfg, p["lnx"], x)
        ck = jnp.einsum("bsd,dhe->bshe", enc_out, p["cross"]["wk"])
        cv = jnp.einsum("bsd,dhe->bshe", enc_out, p["cross"]["wv"])
        if cfg.qkv_bias:
            ck, cv = ck + p["cross"]["bk"], cv + p["cross"]["bv"]
        a, _ = attention_apply(cfg, p["cross"], hx, rules=rules,
                               cross_kv=(ck, cv))
        x = _res(cfg, x, a)
        if want_cache:
            from repro.models.layers import kv_quant
            cache = {**cache, "xk": kv_quant(cfg, ck), "xv": kv_quant(cfg, cv)}
    x, aux, _ = _ffn(cfg, kind, p, x, rules)
    return x, cache, aux


def _window_cache(cfg, kv, w):
    """Ring-buffer (slot = pos % w) cache from a full prefill kv."""
    from repro.models.layers import kv_quant
    k, v = kv_quant(cfg, kv[0]), kv_quant(cfg, kv[1])
    S = k.shape[1]
    if S <= w:
        pad = w - S
        return {"k": jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))),
                "v": jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))}
    pos = jnp.arange(S - w, S)
    slots = pos % w
    ck = jnp.zeros((k.shape[0], w) + k.shape[2:], k.dtype).at[:, slots].set(
        k[:, pos])
    cv = jnp.zeros((v.shape[0], w) + v.shape[2:], v.dtype).at[:, slots].set(
        v[:, pos])
    return {"k": ck, "v": cv}


def block_decode(cfg, kind: str, p, x, cache, pos, *,
                 rules: Rules = NO_RULES, block_table=None,
                 win_block_table=None):
    """Decode block step. x: (B, T, d) — T == 1 for plain decode; paged
    blocks also take T > 1 speculative verify blocks (pos is the first
    row's position; see layers.attention_decode). Returns (x, new_cache,
    rows): ``rows`` counts the routed rows of each held expert of a
    dropless routed FFN (else None).
    block_table switches the full-attention cache entries to the
    paged-pool layout; win_block_table does the same for local_attn
    layers (sliding-window pages, recycled as they leave the window —
    without it local_attn runs the dense ring buffer, single-token only).
    Recurrent kinds (ssm/ssm_moe/rglru) hold per-slot state, not pages:
    T > 1 runs a T-step recurrence returning checkpointed states (see
    ssm_decode / rglru_decode)."""
    h = _norm(cfg, p["ln1"], x)
    if kind in ("attn_mlp", "attn_moe", "dec"):
        a, new_cache = attention_decode(cfg, p["attn"], h,
                                        {"k": cache["k"], "v": cache["v"]},
                                        pos, rules=rules,
                                        block_table=block_table)
    elif kind == "local_attn":
        a, new_cache = attention_decode(cfg, p["attn"], h,
                                        {"k": cache["k"], "v": cache["v"]},
                                        pos, rules=rules,
                                        window=cfg.hybrid.window,
                                        block_table=win_block_table)
    elif kind in ("ssm", "ssm_moe"):
        a, new_cache = ssm.ssm_decode(cfg, p["mixer"], h, cache, rules=rules)
        if kind == "ssm":
            return _res(cfg, x, a), new_cache, None
    elif kind == "rglru":
        a, new_cache = griffin.rglru_decode(cfg, p["mixer"], h, cache,
                                            rules=rules)
    else:
        raise ValueError(kind)
    x = _res(cfg, x, a)
    if kind == "dec":
        new_cache = dict(new_cache)
        hx = _norm(cfg, p["lnx"], x)
        a, _ = attention_decode(cfg, p["cross"], hx,
                                {"k": cache["xk"], "v": cache["xv"]},
                                pos, rules=rules, cross=True)
        x = _res(cfg, x, a)
        new_cache["xk"], new_cache["xv"] = cache["xk"], cache["xv"]
    x, _, rows = _ffn(cfg, kind, p, x, rules)
    return x, new_cache, rows


# ---------------------------------------------------------------------------
# Stacks (scan over super-blocks + tail)
# ---------------------------------------------------------------------------


def stack_init(mk: Maker, cfg, kinds: Tuple[str, ...], n_super: int,
               tail: Tuple[str, ...], key=None) -> Dict[str, Any]:
    """Stacked per-pattern-position params + tail params."""
    if mk.mode == "axes":
        one = {str(j): block_init(mk, cfg, k) for j, k in enumerate(kinds)}
        scan = jax.tree.map(lambda a: ("layers," + a) if a else "layers", one)
        return {"scan": scan,
                "tail": [block_init(mk, cfg, k) for k in tail]}
    keys = jax.random.split(key, n_super)

    def init_one(k):
        mkk = Maker("init", k, mk.dtype)
        return {str(j): block_init(mkk, cfg, kd) for j, kd in enumerate(kinds)}

    scan = jax.vmap(init_one)(keys) if n_super > 0 else {}
    tailp = []
    for t, kd in enumerate(tail):
        mkk = Maker("init", jax.random.fold_in(key, 10_000 + t), mk.dtype)
        tailp.append(block_init(mkk, cfg, kd))
    return {"scan": scan, "tail": tailp}


def _remat(cfg, fn):
    if cfg.remat == "none":
        return fn
    policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
              if cfg.remat == "dots" else None)
    return jax.checkpoint(fn, policy=policy)


def stack_apply(cfg, params, x, kinds, tail, *, rules=NO_RULES,
                positions=None, enc_out=None, want_cache=False, max_len=None,
                prefix_kv=None, prefix_len=None, length=None,
                paged_kv=False):
    """Returns (x, caches, aux_sum). caches: {"scan": {j: stacked}, "tail": [..]}

    prefix_kv (same tree shape as the caches: {"scan": {j: {"k","v"}},
    "tail": [{"k","v"}]}, scan entries stacked (L, B, Pb, KV, D)) +
    prefix_len switch every full-attention block to suffix-only prefill
    against that reused KV; the per-layer slices ride the layer scan
    alongside the params."""

    def body(carry, sl):
        h, aux_acc = carry
        pslice, pfx = sl if prefix_kv is not None else (sl, None)
        caches = {}
        for j, kd in enumerate(kinds):
            pref = None
            if pfx is not None and kd in ("attn_mlp", "attn_moe"):
                pref = (pfx[str(j)]["k"], pfx[str(j)]["v"], prefix_len)
            h, c, aux = block_apply(cfg, kd, pslice[str(j)], h, rules=rules,
                                    positions=positions, enc_out=enc_out,
                                    want_cache=want_cache, max_len=max_len,
                                    prefix=pref, length=length,
                                    paged_kv=paged_kv)
            caches[str(j)] = c if c is not None else 0
            for k, v in aux.items():
                aux_acc[k] = aux_acc.get(k, 0.0) + v
        return (h, aux_acc), caches

    aux0 = {"lb_loss": jnp.zeros((), jnp.float32),
            "z_loss": jnp.zeros((), jnp.float32)}
    n_super = jax.tree.leaves(params["scan"])[0].shape[0] if params["scan"] else 0
    if n_super:
        xs = params["scan"] if prefix_kv is None \
            else (params["scan"], prefix_kv["scan"])
        (x, aux0), scan_caches = jax.lax.scan(_remat(cfg, body), (x, aux0),
                                              xs)
    else:
        scan_caches = {}
    tail_caches = []
    for t, (tp, kd) in enumerate(zip(params["tail"], tail)):
        pref = None
        if prefix_kv is not None and kd in ("attn_mlp", "attn_moe"):
            e = prefix_kv["tail"][t]
            pref = (e["k"], e["v"], prefix_len)
        x, c, aux = block_apply(cfg, kd, tp, x, rules=rules,
                                positions=positions, enc_out=enc_out,
                                want_cache=want_cache, max_len=max_len,
                                prefix=pref, length=length,
                                paged_kv=paged_kv)
        tail_caches.append(c if c is not None else 0)
        for k, v in aux.items():
            aux0[k] = aux0.get(k, 0.0) + v
    return x, {"scan": scan_caches, "tail": tail_caches}, aux0


def stack_decode(cfg, params, x, caches, pos, kinds, tail, *, rules=NO_RULES,
                 block_table=None, win_block_table=None,
                 expert_rows: bool = False):
    """Decode the whole stack one step. x: (B, T, d); T > 1 (a speculative
    multi-token block) requires every attention layer on a paged cache
    layout (block_table for full attention, win_block_table for sliding
    windows); recurrent layers then return checkpointed per-row states —
    see block_decode. ``expert_rows`` adds a third result: the routed
    rows of each held expert, (routed layers, held) int32, layers in
    stack order (None where no layer routes dropless)."""
    def body(h, sl):
        pslice, cslice = sl
        new_c, rows = {}, []
        for j, kd in enumerate(kinds):
            h, nc, r = block_decode(cfg, kd, pslice[str(j)], h,
                                    cslice[str(j)], pos, rules=rules,
                                    block_table=block_table,
                                    win_block_table=win_block_table)
            new_c[str(j)] = nc
            if r is not None:
                rows.append(r)
        return h, (new_c, rows)

    rows = []
    n_super = jax.tree.leaves(params["scan"])[0].shape[0] if params["scan"] else 0
    if n_super:
        x, (new_scan, scan_rows) = jax.lax.scan(
            body, x, (params["scan"], caches["scan"]))
        if scan_rows:           # (n_super, held) per pattern position
            rows.append(jnp.stack(scan_rows, 1).reshape(
                -1, scan_rows[0].shape[-1]))
    else:
        new_scan = {}
    new_tail = []
    for tp, kd, tc in zip(params["tail"], tail, caches["tail"]):
        x, nc, r = block_decode(cfg, kd, tp, x, tc, pos, rules=rules,
                                block_table=block_table,
                                win_block_table=win_block_table)
        new_tail.append(nc)
        if r is not None:
            rows.append(r[None])
    out = (x, {"scan": new_scan, "tail": new_tail})
    if expert_rows:
        out += (jnp.concatenate(rows) if rows else None,)
    return out
