"""Mamba2 (SSD — state-space duality) mixer.

Training/prefill uses the chunked SSD algorithm (arXiv:2405.21060 §6): the
sequence is split into chunks; intra-chunk terms are batched GeMMs (exactly
the balanced 3D-tile case Voltra's GeMM core targets — see DESIGN.md
§Arch-applicability) and the inter-chunk recurrence is a short scan over
chunk states. Decode is the O(1) recurrent step. Both end in Mamba-2's
gated RMSNorm, norm(y * silu(z)), before the output projection.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models.layers import Maker, norm_apply, norm_init
from repro.parallel.sharding import NO_RULES, Rules


def dims(cfg):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    nheads = d_inner // s.head_dim
    conv_dim = d_inner + 2 * s.num_groups * s.state_dim
    return d_inner, nheads, conv_dim


def ssm_init(mk: Maker, cfg) -> Dict[str, Any]:
    s = cfg.ssm
    d = cfg.d_model
    d_inner, nheads, conv_dim = dims(cfg)
    in_dim = 2 * d_inner + 2 * s.num_groups * s.state_dim + nheads
    return {
        "in_proj": mk((d, in_dim), "wembed,wff", scale=d ** -0.5),
        "conv_w": mk((s.conv_width, conv_dim), "", scale=s.conv_width ** -0.5),
        "conv_b": mk((conv_dim,), "", zeros=True),
        "A_log": mk((nheads,), "heads", ones=True, dtype=jnp.float32),
        "D": mk((nheads,), "heads", ones=True, dtype=jnp.float32),
        "dt_bias": mk((nheads,), "heads", zeros=True, dtype=jnp.float32),
        "norm": norm_init(mk, d_inner, "rmsnorm"),
        "out_proj": mk((d_inner, d), "wff,wembed", scale=d_inner ** -0.5),
    }


def _split(cfg, zxbcdt):
    s = cfg.ssm
    d_inner, nheads, _ = dims(cfg)
    gn = s.num_groups * s.state_dim
    z, xBC, dt = jnp.split(zxbcdt, [d_inner, 2 * d_inner + 2 * gn], axis=-1)
    return z, xBC, dt  # dt: (..., nheads)


def _gated_norm(cfg, p, y, z):
    """Mamba-2's gated RMSNorm: the gate before the norm, in float32."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    return norm_apply(p["norm"], g, "rmsnorm", cfg.norm_eps).astype(y.dtype)


def _conv(cfg, p, xBC):
    """Causal depthwise conv over sequence axis 1."""
    w = cfg.ssm.conv_width
    out = p["conv_b"]
    for i in range(w):
        shift = w - 1 - i
        xs = jnp.pad(xBC, ((0, 0), (shift, 0), (0, 0)))[:, : xBC.shape[1]]
        out = out + p["conv_w"][i] * xs
    return jax.nn.silu(out)


def _ssd_scan(x, dt, A, B, C, chunk: int):
    """Chunked SSD. x:(b,l,h,p) dt:(b,l,h) A:(h,) B,C:(b,l,g,n).
    Returns (y:(b,l,h,p), final_state:(b,h,p,n))."""
    b, l, h, pdim = x.shape
    g, n = B.shape[2], B.shape[3]
    Q = min(chunk, l)
    pad = (-l) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = (l + pad) // Q
    xc = x.reshape(b, nc, Q, h, pdim).transpose(1, 0, 2, 3, 4)       # (nc,b,Q,h,p)
    dtc = dt.reshape(b, nc, Q, h).transpose(1, 0, 3, 2)               # (nc,b,h,Q)
    Bc = B.reshape(b, nc, Q, g, n).transpose(1, 0, 2, 3, 4)
    Cc = C.reshape(b, nc, Q, g, n).transpose(1, 0, 2, 3, 4)
    rep = h // g

    def step(S, inp):
        xq, dtq, Bq, Cq = inp                 # (b,Q,h,p) (b,h,Q) (b,Q,g,n) x2
        dA = dtq * A[None, :, None]           # (b,h,Q)
        cum = jnp.cumsum(dA, -1)
        # intra-chunk (diagonal) term
        seg = cum[..., :, None] - cum[..., None, :]                   # (b,h,Q,Q)
        Lmask = jnp.where(jnp.tril(jnp.ones((Q, Q), bool)), jnp.exp(seg), 0.0)
        CB = jnp.einsum("bqgn,bkgn->bgqk", Cq, Bq,
                        preferred_element_type=jnp.float32)
        CBh = jnp.repeat(CB, rep, axis=1)                             # (b,h,Q,Q)
        scores = CBh * Lmask * dtq[:, :, None, :]
        y = jnp.einsum("bhqk,bkhp->bqhp", scores.astype(xq.dtype), xq,
                       preferred_element_type=jnp.float32)
        # inter-chunk contribution from entering state S: (b,h,p,n)
        Ch = jnp.repeat(Cq, rep, axis=2)                              # (b,Q,h,n)
        y = y + jnp.einsum("bqhn,bhpn->bqhp", Ch, S,
                           preferred_element_type=jnp.float32) * jnp.exp(
            cum).transpose(0, 2, 1)[..., None]
        # chunk state update
        decay_end = jnp.exp(cum[..., -1:] - cum)                      # (b,h,Q)
        Bh = jnp.repeat(Bq, rep, axis=2)                              # (b,Q,h,n)
        dstate = jnp.einsum("bqhn,bhq,bqhp->bhpn", Bh, decay_end * dtq,
                            xq, preferred_element_type=jnp.float32)
        S_next = jnp.exp(cum[..., -1])[..., None, None] * S + dstate
        return S_next, y.astype(xq.dtype)

    S0 = jnp.zeros((b, h, pdim, n), jnp.float32)
    S_final, ys = jax.lax.scan(step, S0, (xc, dtc, Bc, Cc))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(b, nc * Q, h, pdim)[:, :l]
    return y, S_final


def ssm_apply(cfg, p, x, *, rules: Rules = NO_RULES,
              return_state: bool = False, length=None):
    """Full-sequence Mamba2 mixer. x: (B, S, d).

    ``length`` (scalar or (B,), may be traced): number of REAL tokens when
    ``x`` is right-padded to a bucket size (paged bucketed prefill).
    Padded positions get dt = 0, which makes their state update the
    identity (decay exp(dt*A) = 1, injection dt*B*x = 0), so the returned
    final state is exactly the state at position length - 1; the conv
    state gathers the last real rows. Real-position outputs are untouched
    (the SSD scan and conv are causal)."""
    s = cfg.ssm
    d_inner, nheads, conv_dim = dims(cfg)
    zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    z, xBC_raw, dt = _split(cfg, zxbcdt)
    xBC = _conv(cfg, p, xBC_raw)
    gn = s.num_groups * s.state_dim
    xin, B_, C_ = jnp.split(xBC, [d_inner, d_inner + gn], axis=-1)
    b, l = x.shape[0], x.shape[1]
    xin = xin.reshape(b, l, nheads, s.head_dim)
    xin = rules.cons(xin, "batch,seq,heads")
    B_ = B_.reshape(b, l, s.num_groups, s.state_dim)
    C_ = C_.reshape(b, l, s.num_groups, s.state_dim)
    dt_ = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    if length is not None:
        lv = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (b,))
        live = (jnp.arange(l)[None, :] < lv[:, None])[..., None]
        dt_ = jnp.where(live, dt_, 0.0)
    A = -jnp.exp(p["A_log"])
    y, S_final = _ssd_scan(xin, dt_, A, B_, C_, s.chunk)
    y = y + p["D"][None, None, :, None].astype(y.dtype) * xin
    y = _gated_norm(cfg, p, y.reshape(b, l, d_inner), z)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])
    out = rules.cons(out, "batch,seq,embed")
    if return_state:
        w = cfg.ssm.conv_width
        # conv state: last (w-1) *pre-activation* xBC inputs
        if length is not None:
            from repro.models.griffin import _gather_conv_state
            conv_state = _gather_conv_state(xBC_raw, length, w, l)
        else:
            conv_state = xBC_raw[:, -(w - 1):]
            pad = (w - 1) - conv_state.shape[1]
            if pad > 0:
                conv_state = jnp.pad(conv_state, ((0, 0), (pad, 0), (0, 0)))
        return out, {"ssm": S_final.astype(jnp.float32),
                     "conv": conv_state.astype(x.dtype)}
    return out


def ssm_cache_init(cfg, batch: int):
    s = cfg.ssm
    d_inner, nheads, conv_dim = dims(cfg)
    return {
        "ssm": jnp.zeros((batch, nheads, s.head_dim, s.state_dim), jnp.float32),
        "conv": jnp.zeros((batch, s.conv_width - 1, conv_dim),
                          jnp.dtype(cfg.dtype)),
    }


def _ssm_token_step(cfg, p, carry, zxbcdt):
    """One recurrent token: (S, conv) x (B, in_dim) -> (S', conv', y)."""
    s = cfg.ssm
    d_inner, nheads, _ = dims(cfg)
    S_prev, conv_prev = carry
    z, xBC, dt = _split(cfg, zxbcdt)
    hist = jnp.concatenate([conv_prev, xBC[:, None]], 1)      # (B, w, conv)
    conv_out = jax.nn.silu(jnp.einsum("bwc,wc->bc", hist, p["conv_w"])
                           + p["conv_b"])
    new_conv = hist[:, 1:]
    gn = s.num_groups * s.state_dim
    xin, B_, C_ = jnp.split(conv_out, [d_inner, d_inner + gn], axis=-1)
    bsz = zxbcdt.shape[0]
    xin = xin.reshape(bsz, nheads, s.head_dim)
    B_ = B_.reshape(bsz, s.num_groups, s.state_dim)
    C_ = C_.reshape(bsz, s.num_groups, s.state_dim)
    rep = nheads // s.num_groups
    Bh = jnp.repeat(B_, rep, axis=1).astype(jnp.float32)
    Ch = jnp.repeat(C_, rep, axis=1).astype(jnp.float32)
    dt_ = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])     # (B, h)
    A = -jnp.exp(p["A_log"])
    dA = jnp.exp(dt_ * A)                                            # (B, h)
    xf = xin.astype(jnp.float32)
    S = dA[..., None, None] * S_prev + jnp.einsum(
        "bh,bhn,bhp->bhpn", dt_, Bh, xf)
    y = jnp.einsum("bhn,bhpn->bhp", Ch, S) + p["D"][None, :, None] * xf
    y = _gated_norm(cfg, p, y.reshape(bsz, d_inner).astype(zxbcdt.dtype), z)
    return (S, new_conv), y


def ssm_decode(cfg, p, x, cache, *, rules: Rules = NO_RULES):
    """Recurrent decode step. x: (B, T, d) — T == 1 is the plain
    one-token step with plain state shapes. T > 1 (a speculative verify
    block) runs T token steps and returns CHECKPOINTED states — every
    leaf gains a T axis at position 1 ({"ssm": (B, T, h, p, n), "conv":
    (B, T, w-1, conv)}), state t being the state AFTER block row t — so
    the serving engine can roll back to any accepted prefix with one
    gather (the recurrent analogue of PageAllocator.truncate_to)."""
    T = x.shape[1]
    zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    if T == 1:
        (S, new_conv), y = _ssm_token_step(
            cfg, p, (cache["ssm"], cache["conv"]), zxbcdt[:, 0])
        out = jnp.einsum("be,ed->bd", y, p["out_proj"])[:, None]
        return (rules.cons(out, "batch,seq,embed"),
                {"ssm": S, "conv": new_conv})

    def step(carry, zx_t):
        carry2, y = _ssm_token_step(cfg, p, carry, zx_t)
        return carry2, (carry2[0], carry2[1], y)

    _, (Ss, convs, ys) = jax.lax.scan(step, (cache["ssm"], cache["conv"]),
                                      zxbcdt.transpose(1, 0, 2))
    out = jnp.einsum("tbe,ed->btd", ys, p["out_proj"])
    return (rules.cons(out, "batch,seq,embed"),
            {"ssm": Ss.transpose(1, 0, 2, 3, 4),
             "conv": convs.transpose(1, 0, 2, 3)})
