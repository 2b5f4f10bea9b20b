"""Unified model API: init / forward / loss / prefill / decode / cache.

Every assigned architecture is driven through these six functions; the
launcher, trainer, serving engine and dry-run all sit on top of them.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models import transformer as tfm
from repro.models.layers import Maker, norm_apply, norm_init
from repro.parallel.sharding import NO_RULES, Rules

# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def _build(cfg, mk: Maker, key=None) -> Dict[str, Any]:
    vp = tfm.padded_vocab(cfg.vocab)
    d = cfg.d_model
    kinds = tfm.pattern_for(cfg)
    n_super, tail = tfm.layer_plan(cfg)
    p: Dict[str, Any] = {
        "embed": mk((vp, d), "wvocab,wembed", scale=0.02),
        "final_norm": norm_init(mk, d, cfg.norm),
        "blocks": tfm.stack_init(
            mk, cfg, kinds, n_super, tail,
            key=None if mk.mode == "axes" else jax.random.fold_in(key, 1)),
    }
    if not cfg.tie_embeddings:
        p["head"] = mk((d, vp), "wembed,wvocab", scale=d ** -0.5)
    if cfg.is_encdec:
        ek = None if mk.mode == "axes" else jax.random.fold_in(key, 2)
        p["enc"] = {
            "blocks": tfm.stack_init(mk, cfg, ("enc",), cfg.enc_layers, (),
                                     key=ek),
            "final_norm": norm_init(mk, d, cfg.norm),
        }
    return p


def init_params(cfg, key) -> Dict[str, Any]:
    """Random weights from ``key``, made in one jitted program: run
    eagerly, the layer-stacked init dispatches one small program per op
    and materializes each weight stack in f32 before the cast (GBs at
    published widths)."""
    return _init_params(cfg, key)


@functools.partial(jax.jit, static_argnums=0)
def _init_params(cfg, key) -> Dict[str, Any]:
    mk = Maker("init", key, jnp.dtype(cfg.dtype))
    return _build(cfg, mk, key)


def param_axes(cfg) -> Dict[str, Any]:
    return _build(cfg, Maker("axes"))


def param_shapes(cfg):
    return jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _embed_tokens(cfg, params, tokens):
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    return x


def _scale_logits(cfg, logits):
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def _logits(cfg, params, x):
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    return _scale_logits(
        cfg, jnp.einsum("bsd,dv->bsv", x, w).astype(jnp.float32))


def _final_norm(cfg, p, x):
    return norm_apply(p, x, cfg.norm, cfg.norm_eps)


def _encode(cfg, params, enc_embeds, rules):
    x, _, _ = tfm.stack_apply(cfg, params["enc"]["blocks"], enc_embeds,
                              ("enc",), (), rules=rules)
    return _final_norm(cfg, params["enc"]["final_norm"], x)


def forward_hidden(cfg, params, batch: Dict[str, Any], *,
                   rules: Rules = NO_RULES, want_cache: bool = False,
                   max_len=None, prefix_kv=None, prefix_len=None,
                   length=None, paged_kv: bool = False):
    """batch: {tokens [, frontend_embeds | enc_embeds]} -> (hidden, caches,
    aux). Sequence layout for VLM: [frontend_embeds | token embeds].

    prefix_kv + prefix_len (traced scalar): `tokens` are the SUFFIX of a
    request whose first prefix_len tokens' KV is being reused from the
    paged pool (prefix sharing); positions and causal masks are offset
    accordingly. Attention-only stacks only — recurrent state cannot be
    reconstructed from cached KV.

    length (scalar/(B,), may be traced) + paged_kv: bucketed prefill for
    stacks with recurrent / windowed state — `tokens` is right-padded to
    a bucket size and only the first `length` are real. Recurrent blocks
    mask their state updates past `length` (the returned state is the
    state at length - 1) and local_attn returns full-sequence kv for the
    paged window scatter instead of a ring buffer (see block_apply)."""
    kinds = tfm.pattern_for(cfg)
    _, tail = tfm.layer_plan(cfg)
    if prefix_kv is not None:
        assert set(kinds) <= set(PAGEABLE_KINDS), \
            f"prefix reuse needs an attention-only stack, got {kinds}"
    x = _embed_tokens(cfg, params, batch["tokens"])
    if cfg.frontend == "patch" and "frontend_embeds" in batch:
        x = jnp.concatenate(
            [batch["frontend_embeds"].astype(x.dtype), x], axis=1)
    x = rules.cons(x, "batch,seq,embed")
    S = x.shape[1]
    positions = jnp.arange(S)[None, :]
    if prefix_len is not None:
        positions = positions + jnp.asarray(prefix_len, jnp.int32)
    enc_out = None
    if cfg.is_encdec:
        enc_out = _encode(cfg, params, batch["enc_embeds"].astype(x.dtype),
                          rules)
    x, caches, aux = tfm.stack_apply(cfg, params["blocks"], x, kinds, tail,
                                     rules=rules, positions=positions,
                                     enc_out=enc_out, want_cache=want_cache,
                                     max_len=max_len, prefix_kv=prefix_kv,
                                     prefix_len=prefix_len, length=length,
                                     paged_kv=paged_kv)
    x = _final_norm(cfg, params["final_norm"], x)
    return x, caches, aux


def forward(cfg, params, batch: Dict[str, Any], *, rules: Rules = NO_RULES,
            want_cache: bool = False, max_len=None):
    """Full-sequence logits (small models / tests; training uses the
    blockwise-CE path in loss_fn to avoid materializing (B, S, vocab))."""
    x, caches, aux = forward_hidden(cfg, params, batch, rules=rules,
                                    want_cache=want_cache, max_len=max_len)
    logits = _logits(cfg, params, x)
    logits = rules.cons(logits, "batch,seq,vocab")
    return logits, caches, aux


CE_CHUNK = 512


def _ce_chunk(cfg, params, x, labels, rules):
    """CE over one sequence chunk; logits (B, c, Vp) live only inside."""
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = _scale_logits(
        cfg, jnp.einsum("bsd,dv->bsv", x, w).astype(jnp.float32))
    logits = rules.cons(logits, "batch,seq,vocab")
    vp = logits.shape[-1]
    if vp > cfg.vocab:
        logits = jnp.where(jnp.arange(vp) < cfg.vocab, logits, -1e30)
    mask = (labels >= 0).astype(jnp.float32)
    lab = jnp.maximum(labels, 0)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, lab[..., None], -1)[..., 0]
    return ((lse - ll) * mask).sum(), mask.sum()


def loss_fn(cfg, params, batch, *, rules: Rules = NO_RULES):
    """Next-token CE (labels aligned: labels[t] is the target of logits[t]).
    Blockwise over sequence chunks: full (B, S, vocab) logits are never
    materialized (checkpointed scan recomputes per-chunk logits in bwd).
    VLM: loss only over the text segment (last `len(labels)` positions)."""
    x, _, aux = forward_hidden(cfg, params, batch, rules=rules)
    labels = batch["labels"]
    if x.shape[1] != labels.shape[1]:  # VLM frontend positions carry no loss
        x = x[:, -labels.shape[1]:]
    B, S, _ = x.shape
    c = min(CE_CHUNK, S)
    pad = (-S) % c
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    nc = (S + pad) // c
    xc = x.reshape(B, nc, c, -1).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, nc, c).transpose(1, 0, 2)

    def body(carry, inp):
        tot, cnt = carry
        xs, ls = inp
        t, n = jax.checkpoint(
            lambda a, b: _ce_chunk(cfg, params, a, b, rules))(xs, ls)
        return (tot + t, cnt + n), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())),
                                 (xc, lc))
    ce = tot / jnp.maximum(cnt, 1.0)
    loss = ce
    if cfg.moe is not None and cfg.moe.num_experts > 0:
        loss = loss + 0.01 * aux["lb_loss"] + 0.001 * aux["z_loss"]
    return loss, {"ce": ce, **aux}


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------


def prefill(cfg, params, batch, *, rules: Rules = NO_RULES, max_len=None,
            length=None, prefix_kv=None, prefix_len=None,
            paged_kv: bool = False):
    """Run the full prompt; returns (last_logits, cache, next_pos). Full-attn
    kv caches are padded out to `max_len` slots for subsequent decoding.
    Logits are computed for the LAST position only (the (B, S, vocab) tensor
    is never materialized — PDMA-style residency at the serving level).

    `length` (scalar or (B,) int32, may be traced) marks the number of REAL
    tokens when `tokens` is right-padded to a bucket size: logits are taken
    at position length-1 and next_pos = length. Causal masking already
    keeps positions < length independent of the padding, so one trace
    serves every prompt length in the bucket (the serving engine's
    mixed-grained-prefetch analogue). Stacks with recurrent / windowed
    state additionally need ``paged_kv=True``: recurrent blocks then mask
    state updates past ``length`` (so the returned state is the state at
    length - 1 — padding never leaks into it) and local_attn blocks
    return full-sequence kv for the paged window scatter; WITHOUT
    paged_kv those callers must pass exact-length tokens (the dense
    engine's ring buffers carry padding into their state otherwise).

    prefix_kv + prefix_len (traced): suffix-only prefill — `tokens` and
    `length` describe only the part of the prompt AFTER a prefix whose KV
    is reused from the paged pool (see forward_hidden / prefix_cache.py).
    The returned cache holds the suffix k/v only; returned pos counts
    suffix tokens (callers add prefix_len)."""
    x, caches, _ = forward_hidden(cfg, params, batch, rules=rules,
                                  want_cache=True, max_len=max_len,
                                  prefix_kv=prefix_kv, prefix_len=prefix_len,
                                  length=length if paged_kv else None,
                                  paged_kv=paged_kv)
    B, S = x.shape[0], x.shape[1]
    if length is None:
        logits = _logits(cfg, params, x[:, -1:])[:, 0]
        pos = jnp.full((B,), S, jnp.int32)
    else:
        length = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))
        idx = jnp.clip(length - 1, 0, S - 1)[:, None, None]
        xl = jnp.take_along_axis(
            x, jnp.broadcast_to(idx, (B, 1, x.shape[-1])), axis=1)
        logits = _logits(cfg, params, xl)[:, 0]
        pos = length
    return logits, caches, pos


def decode_step(cfg, params, cache, tokens, pos, *,
                rules: Rules = NO_RULES, block_table=None,
                win_block_table=None, expert_rows: bool = False):
    """tokens: (B, T) int32 — T == 1 for plain decode, T > 1 for a
    speculative multi-token verify block (paged caches only; token t of
    request b sits at absolute position pos[b] + t). pos: (B,) position of
    the FIRST new token. -> (logits, new_cache); logits are (B, vocab)
    when T == 1 (the historical contract every serving loop relies on)
    and (B, T, vocab) when T > 1 — one row per block position, which is
    exactly what greedy speculative acceptance consumes.
    block_table: (B, n_blocks) int32 switches full-attention cache entries
    to the shared paged pool layout (see paged_cache_init); attention then
    runs the block-table indirection inside the Pallas flash-decode kernel
    (kernels/ops.paged_attention) unless cfg.paged_attn_impl == "gather"
    pins the dense-gather baseline. win_block_table: same for local_attn
    layers (sliding-window pages, recycled as they slide out of the
    window); without it local_attn runs the dense ring buffer —
    single-token only, so a T > 1 block on a windowed stack WITHOUT the
    paged window layout is rejected here with a ValueError naming the
    layer kind (instead of the bare shape assert it used to die with
    deep inside the jit trace).
    expert_rows=True adds a third result: the routed rows of each held
    expert of every dropless routed layer, (layers, held) int32 (None
    where there is none; transformer.stack_decode)."""
    kinds = tfm.pattern_for(cfg)
    _, tail = tfm.layer_plan(cfg)
    if tokens.shape[1] > 1:
        present = dict.fromkeys(tuple(kinds) + tuple(tail))
        bad = [k for k, need in
               (("attn_mlp", block_table), ("attn_moe", block_table),
                ("local_attn", win_block_table))
               if k in present and need is None]
        bad += [k for k in ("dec", "enc") if k in present]
        if bad:
            raise ValueError(
                f"multi-token decode blocks (T={tokens.shape[1]}) need "
                f"every attention layer on a paged cache layout, but "
                f"layer kind(s) {bad} have none: pass block_table for "
                f"full attention and win_block_table for local_attn "
                f"(the dense ring buffer is single-token — it has "
                f"already overwritten the keys older block rows attend "
                f"to)")
    x = _embed_tokens(cfg, params, tokens)
    x = rules.cons(x, "batch,seq,embed")
    x, new_cache, *rows = tfm.stack_decode(
        cfg, params["blocks"], x, cache, pos, kinds, tail, rules=rules,
        block_table=block_table, win_block_table=win_block_table,
        expert_rows=expert_rows)
    x = _final_norm(cfg, params["final_norm"], x)
    if tokens.shape[1] == 1:
        logits = rules.cons(_logits(cfg, params, x)[:, 0], "batch,vocab")
    else:
        logits = rules.cons(_logits(cfg, params, x), "batch,seq,vocab")
    return (logits, new_cache, *rows)


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------


def _block_cache_init(cfg, kind: str, batch: int, seq_len: int):
    from repro.models import griffin, ssm
    dt = jnp.dtype(cfg.kv_cache_dtype)   # int8 cache opt-in (§Perf C4)
    kv, hd = cfg.kv_heads, cfg.resolved_head_dim
    if kind in ("attn_mlp", "attn_moe"):
        return {"k": jnp.zeros((batch, seq_len, kv, hd), dt),
                "v": jnp.zeros((batch, seq_len, kv, hd), dt)}
    if kind == "dec":
        return {"k": jnp.zeros((batch, seq_len, kv, hd), dt),
                "v": jnp.zeros((batch, seq_len, kv, hd), dt),
                "xk": jnp.zeros((batch, seq_len, kv, hd), dt),
                "xv": jnp.zeros((batch, seq_len, kv, hd), dt)}
    if kind == "local_attn":
        w = cfg.hybrid.window  # ring buffer is always window-sized
        return {"k": jnp.zeros((batch, w, kv, hd), dt),
                "v": jnp.zeros((batch, w, kv, hd), dt)}
    if kind in ("ssm", "ssm_moe"):
        return ssm.ssm_cache_init(cfg, batch)
    if kind == "rglru":
        return griffin.rglru_cache_init(cfg, batch)
    raise ValueError(kind)


def cache_init(cfg, batch: int, seq_len: int):
    kinds = tfm.pattern_for(cfg)
    n_super, tail = tfm.layer_plan(cfg)

    def stacked(kind):
        one = _block_cache_init(cfg, kind, batch, seq_len)
        return jax.tree.map(
            lambda a: jnp.zeros((n_super,) + a.shape, a.dtype), one)

    scan = {str(j): stacked(k) for j, k in enumerate(kinds)} if n_super else {}
    tailc = [_block_cache_init(cfg, k, batch, seq_len) for k in tail]
    return {"scan": scan, "tail": tailc}


PAGEABLE_KINDS = ("attn_mlp", "attn_moe")       # full-attention page pools
WINDOW_KINDS = ("local_attn",)                  # sliding-window page pools
STATE_KINDS = ("ssm", "ssm_moe", "rglru")       # fixed-size per-slot state
# every block kind the PagedServingEngine can host (encoder-decoder stays
# on the dense engine: cross-attention KV is per-request, not paged)
PAGED_SERVABLE_KINDS = PAGEABLE_KINDS + WINDOW_KINDS + STATE_KINDS


def paged_cache_init(cfg, batch: int, num_pages: int, page_size: int):
    """Cache tree for paged serving: attention k/v entries become a shared
    page pool (num_pages, page_size, KV, D) instead of per-slot dense
    lanes (batch, max_len, KV, D) — full attention AND sliding-window
    (local_attn) layers alike; the windowed layers' pages are recycled by
    the engine as they slide out of the window, so their live footprint
    is O(window) pages per request. Recurrent kinds (ssm/rglru) keep
    fixed-size per-slot state beside the pool — O(1) per slot, nothing to
    page; the engine allocates the slot at admission and rebuilds the
    state by re-prefill on preemption-resume. The pools are indexed by
    the block tables of repro.runtime.kv_cache.PageAllocator (page 0 =
    scratch); one host-side logical->physical mapping per table kind
    (full / windowed) drives every layer of that kind."""
    kinds = tfm.pattern_for(cfg)
    n_super, tail = tfm.layer_plan(cfg)
    unpageable = [k for k in tuple(kinds) + tuple(tail)
                  if k not in PAGED_SERVABLE_KINDS]
    if unpageable:
        raise ValueError(
            f"paged cache cannot host block kind(s) {unpageable}; "
            f"servable kinds are {PAGED_SERVABLE_KINDS}")
    dt = jnp.dtype(cfg.kv_cache_dtype)
    kv, hd = cfg.kv_heads, cfg.resolved_head_dim

    def entry(kind):
        from repro.models import griffin, ssm
        if kind in PAGEABLE_KINDS + WINDOW_KINDS:
            return {"k": jnp.zeros((num_pages, page_size, kv, hd), dt),
                    "v": jnp.zeros((num_pages, page_size, kv, hd), dt)}
        if kind in ("ssm", "ssm_moe"):
            return ssm.ssm_cache_init(cfg, batch)
        return griffin.rglru_cache_init(cfg, batch)

    def stacked(kind):
        return jax.tree.map(
            lambda a: jnp.zeros((n_super,) + a.shape, a.dtype), entry(kind))

    scan = {str(j): stacked(k)
            for j, k in enumerate(kinds)} if n_super else {}
    return {"scan": scan, "tail": [entry(k) for k in tail]}


def state_slot_export(cfg, cache, slot):
    """Serialize one slot's recurrent state (every ssm/rglru layer of a
    paged cache tree) into a detached tree — the swap-out half of the
    host-tier protocol (runtime/host_tier.py): a preempted hybrid request
    carries its state to host RAM instead of rebuilding it by re-prefill.
    Non-state entries are omitted (tail is dict-keyed by entry index so
    the import can realign). ``slot`` may be traced."""
    kinds = tfm.pattern_for(cfg)
    _, tail = tfm.layer_plan(cfg)
    state = set(STATE_KINDS)
    return {
        "scan": {str(j): jax.tree.map(lambda le: le[:, slot],
                                      cache["scan"][str(j)])
                 for j, kd in enumerate(kinds)
                 if kd in state and str(j) in cache["scan"]},
        "tail": {str(i): jax.tree.map(lambda le: le[slot], e)
                 for i, (e, kd) in enumerate(zip(cache["tail"], tail))
                 if kd in state},
    }


def state_slot_import(cfg, cache, slot, state_tree):
    """Restore a ``state_slot_export`` tree into ``slot`` of a paged
    cache — the swap-in half. Dtypes are cast back to each entry's
    storage dtype; non-state entries pass through untouched."""
    kinds = tfm.pattern_for(cfg)
    _, tail = tfm.layer_plan(cfg)
    state = set(STATE_KINDS)

    def w_scan(le, s):              # (L, slots, ..) <- (L, ..)
        return le.at[:, slot].set(s.astype(le.dtype))

    def w_tail(le, s):              # (slots, ..) <- (..)
        return le.at[slot].set(s.astype(le.dtype))

    new_scan = {}
    for j, kd in enumerate(kinds):
        e = cache["scan"].get(str(j))
        if e is None:
            continue
        new_scan[str(j)] = jax.tree.map(w_scan, e,
                                        state_tree["scan"][str(j)]) \
            if kd in state else e
    new_tail = [jax.tree.map(w_tail, e, state_tree["tail"][str(i)])
                if kd in state else e
                for i, (e, kd) in enumerate(zip(cache["tail"], tail))]
    return {"scan": new_scan, "tail": new_tail}


def state_slot_reset(cfg, cache, slot):
    """Zero one slot's recurrent state (every ssm/ssm_moe/rglru layer);
    pages and other slots pass through. ``slot`` may be traced."""
    zeros = jax.tree.map(jnp.zeros_like, state_slot_export(cfg, cache, slot))
    return state_slot_import(cfg, cache, slot, zeros)


def paged_cache_axes(cfg):
    """Logical axes tree matching paged_cache_init structure — the paged
    analogue of cache_axes, used by the tensor-parallel serving plan
    (parallel/tp.py) to shard the page pools over KV heads.

    Page-pool k/v leaves are (num_pages, page_size, KV, D): dim 2 is the
    shard axis (",,kv_heads"); block tables and lengths never appear here
    (they are engine-side and replicated). Recurrent state slots are
    deliberately replicated ("" — NOT cache_axes' "batch,heads"): their
    mixer params stay replicated under the TP plan, so the state must
    match, and at O(slots) scalars per layer there is nothing worth
    sharding."""
    shapes = jax.eval_shape(
        functools.partial(paged_cache_init, cfg, 1, 2, 2))

    def ax(path, leaf):
        names = [str(getattr(k, "key", getattr(k, "name", "")))
                 for k in path]
        leafname = names[-1] if names else ""
        base = ",,kv_heads" if leafname in ("k", "v") else ""
        if "scan" in names:
            base = ("layers," + base) if base else "layers"
        return base

    return jax.tree_util.tree_map_with_path(ax, shapes)


def cache_shapes(cfg, batch: int, seq_len: int):
    return jax.eval_shape(functools.partial(cache_init, cfg, batch, seq_len))


def cache_axes(cfg):
    """Logical axes tree matching cache_init structure."""
    shapes = cache_shapes(cfg, 1, 2)

    def ax(path, leaf):
        names = [getattr(k, "key", getattr(k, "name", "")) for k in path]
        leafname = str(names[-1]) if names else ""
        if leafname in ("k", "v", "xk", "xv"):
            base = "batch,seq,kv_heads"
        elif leafname == "ssm":
            base = "batch,heads"
        elif leafname == "conv":
            base = "batch"
        elif leafname == "h":
            base = "batch,ffn"
        else:
            base = "batch"
        if "scan" in [str(n) for n in names]:
            base = "layers," + base
        return base

    return jax.tree_util.tree_map_with_path(ax, shapes)
