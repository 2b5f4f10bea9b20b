"""Plain float32 forward of Granite 4.0-H (GraniteMoeHybrid), for the
correctness check.

Straight ``jax.numpy``, no kernels, no cache, no batching; it imports
nothing of the program. One call runs one whole sequence and returns
the logits at the positions asked for.

  x = embed[tokens] * embedding_multiplier
  L x [x += r * Mixer(RMSNorm(x));
       x += r * (Routed(RMSNorm(x)) + Shared(RMSNorm(x)))]
  logits = RMSNorm(x) embed^T / logits_scaling        (tied head)

with r the residual multiplier. The mixer is the layer's ``layer_types``
entry:

- ``mamba``: Mamba-2. One projection gives z, x, B, C and dt; x, B and C
  pass a causal depthwise convolution (with bias) and SiLU; dt =
  softplus(dt + dt_bias), A = -exp(A_log). Token by token, for each head
  h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T and y_t = h_t C_t + D x_t
  (the recurrence itself, not the program's chunked scan); then the
  gated RMSNorm, RMSNorm(y * silu(z)), and the output projection.
- ``attention``: GQA with no positional embedding (NoPE): causal
  softmax(q k^T * attention_multiplier), query head h reading key/value
  head h // (heads / kv_heads), no bias.

Routed: the router scores all ``router_experts`` in float32; each token
takes its ``num_experts_per_tok`` best and weights them by the softmax
over those logits. Only the experts this chip holds
(``first_local_expert`` .. + ``num_local_experts``) are computed, densely
for every token and weighted by its gate (zero where the token did not
pick the expert); the others add nothing, as on the chip. Shared: one
SwiGLU of ``shared_intermediate_size``. Every SwiGLU is silu(x Wg) *
(x Wi), then Wd. RMSNorm: x * rsqrt(mean(x^2) + eps) * scale.

``mode="int8"`` or ``"fp8"`` is the control: the same forward with both
inputs of every matrix product rounded to int8, or to fp8 (e4m3), each
scaled by its absolute maximum (activations per row, weights per output
channel, attention operands per row): the precision one step below the
bfloat16 the model is served in.

``plain`` gives the program's parameter tree under this module's names;
``counts`` gives the operations and bytes the cut model needs, from the
configuration's shapes alone: multiply-adds as 2 operations, weights and
KV in bfloat16, recurrent state in float32, and the routed experts at
their expected share under uniform top-k routing.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Iterable

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
Q_CHUNK = 256
BF16, F32 = 2, 4


@dataclasses.dataclass(frozen=True)
class Spec:
    layer_types: tuple
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int                   # one routed expert's width
    shared_ff: int
    vocab: int
    experts: int                # the router's outputs
    held: int                   # experts held here
    first: int                  # the first of them
    top_k: int
    m_heads: int
    m_head_dim: int
    d_state: int
    groups: int
    conv: int
    eps: float
    emb_mult: float
    res_mult: float
    attn_mult: float
    logits_scaling: float

    @classmethod
    def from_model(cls, m: dict) -> "Spec":
        """From a configuration file's ``model`` object (published keys,
        plus ``router_experts`` and ``first_local_expert`` for the cut)."""
        assert m["tie_word_embeddings"] and m["position_embedding_type"] \
            == "nope" and not m["attention_bias"]
        return cls(layer_types=tuple(m["layer_types"]), d=m["hidden_size"],
                   heads=m["num_attention_heads"],
                   kv_heads=m["num_key_value_heads"],
                   head_dim=m["hidden_size"] // m["num_attention_heads"],
                   d_ff=m["intermediate_size"],
                   shared_ff=m["shared_intermediate_size"],
                   vocab=m["vocab_size"], experts=m["router_experts"],
                   held=m["num_local_experts"],
                   first=m["first_local_expert"],
                   top_k=m["num_experts_per_tok"],
                   m_heads=m["mamba_n_heads"], m_head_dim=m["mamba_d_head"],
                   d_state=m["mamba_d_state"], groups=m["mamba_n_groups"],
                   conv=m["mamba_d_conv"], eps=float(m["rms_norm_eps"]),
                   emb_mult=float(m["embedding_multiplier"]),
                   res_mult=float(m["residual_multiplier"]),
                   attn_mult=float(m["attention_multiplier"]),
                   logits_scaling=float(m["logits_scaling"]))

    # -- shapes ---------------------------------------------------------

    @property
    def d_inner(self) -> int:
        return self.m_heads * self.m_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.groups * self.d_state

    @property
    def n_mamba(self) -> int:
        return self.layer_types.count("mamba")

    @property
    def n_attn(self) -> int:
        return self.layer_types.count("attention")

    @property
    def layers(self) -> int:
        return len(self.layer_types)

    @property
    def mamba_params(self) -> int:
        """Weights one token multiplies in a Mamba-2 mixer: in and out
        projections."""
        in_dim = 2 * self.d_inner + 2 * self.groups * self.d_state \
            + self.m_heads
        return self.d * in_dim + self.d_inner * self.d

    @property
    def attn_params(self) -> int:
        hd = self.head_dim
        return (self.d * (self.heads + 2 * self.kv_heads) * hd
                + self.heads * hd * self.d)

    @property
    def expert_params(self) -> int:
        return 3 * self.d * self.d_ff

    @property
    def ffn_params(self) -> int:
        """Weights one token multiplies in a routed FFN besides its routed
        experts: the router and the shared expert."""
        return self.d * self.experts + 3 * self.d * self.shared_ff

    @property
    def experts_here(self) -> float:
        """Held experts a token is expected to run per routed layer."""
        return self.top_k * self.held / self.experts

    @property
    def state_bytes(self) -> int:
        """One slot's recurrent state over every Mamba layer: the f32 SSM
        state and the bf16 conv window."""
        return self.n_mamba * (
            self.m_heads * self.m_head_dim * self.d_state * F32
            + (self.conv - 1) * self.conv_dim * BF16)

    @property
    def kv_bytes_per_token(self) -> int:
        return 2 * self.n_attn * self.kv_heads * self.head_dim * BF16

    # -- counts ---------------------------------------------------------

    def token_flops(self, context: int, logits: bool) -> float:
        """One token through the stack, attending to ``context`` keys
        (itself included); ``logits`` adds the output head."""
        f = 2 * (self.n_mamba * self.mamba_params
                 + self.n_attn * self.attn_params
                 + self.layers * (self.ffn_params
                                  + self.experts_here * self.expert_params))
        # the conv, and the recurrence: update and readout, one
        # multiply-add each per state element
        f += self.n_mamba * (2 * self.conv * self.conv_dim + 4 * self.m_heads
                             * self.m_head_dim * self.d_state)
        f += 4 * self.n_attn * self.heads * self.head_dim * context
        if logits:
            f += 2 * self.d * self.vocab
        return float(f)

    def prefill_flops(self, n: int) -> float:
        """A prompt of ``n`` tokens, causal, logits at the last one only
        (the recurrence's count for the Mamba layers)."""
        f = n * self.token_flops(0, False)
        f += 4 * self.n_attn * self.heads * self.head_dim * n * (n + 1) / 2
        return float(f + 2 * self.d * self.vocab)

    def decode_flops(self, contexts: Iterable[int]) -> float:
        """One decode step: one token per live request, each at its
        context."""
        return sum(self.token_flops(c, True) for c in contexts)

    def decode_bytes(self, contexts: Iterable[int]) -> float:
        """The least one decode step moves: every weight but the routed
        experts', the held experts its tokens are expected to hit, each
        live slot's recurrent state read and written, and the KV
        (``paged_attn_bytes``)."""
        contexts = list(contexts)
        w = (self.n_mamba * self.mamba_params + self.n_attn * self.attn_params
             + self.layers * self.ffn_params + self.d * self.vocab) * BF16
        w += self.layers * self._hit(len(contexts)) * self.expert_params \
            * BF16
        return float(w + 2 * len(contexts) * self.state_bytes
                     + self.paged_attn_bytes(contexts))

    def paged_attn_bytes(self, contexts: Iterable[int]) -> float:
        """Decode attention over every attention layer for one step: each
        live request's keys and values read once, its query read and
        output written (bfloat16)."""
        qo = 2 * self.heads * self.head_dim * BF16
        return float(sum(c * self.kv_bytes_per_token + self.n_attn * qo
                         for c in contexts))

    def paged_attn_flops(self, contexts: Iterable[int]) -> float:
        return float(sum(4 * self.n_attn * self.heads * self.head_dim * c
                         for c in contexts))

    def _hit(self, tokens: int) -> float:
        """Held experts that ``tokens`` tokens are expected to hit in one
        layer under uniform top-k routing."""
        return self.held * (1 - (1 - self.top_k / self.experts) ** tokens)

    def expert_bytes(self, tokens: int) -> float:
        """One grouped expert call over ``tokens`` tokens, every routed
        layer: the weights of the held experts its rows are expected to
        hit, and the rows in and out (bfloat16)."""
        rows = tokens * self.experts_here
        return float(self.layers * (self._hit(tokens) * self.expert_params
                                    + 2 * rows * self.d) * BF16)

    def expert_flops(self, tokens: int) -> float:
        return float(self.layers * 2 * tokens * self.experts_here
                     * self.expert_params)


def counts(model: dict) -> Spec:
    """The counts of a configuration file's ``model`` object: its
    ``Spec``, whose methods count."""
    return Spec.from_model(model)


def plain(tree) -> dict:
    """The program's parameter tree under this module's names: one entry
    per position of the layer period, each leaf stacked over periods.
    Raises if the program's layout is not such a stack."""
    blk = tree["blocks"]
    if blk["tail"]:
        raise ValueError("the reference computes whole periods only")
    period = []
    for j in range(len(blk["scan"])):
        b = blk["scan"][str(j)]
        moe = b["moe"]
        e = {"ln1": b["ln1"]["scale"], "ln2": b["ln2"]["scale"],
             "router": moe["router"], "wg": moe["wg"], "wi": moe["wi"],
             "wo": moe["wo"], "sg": moe["shared"]["wg"],
             "si": moe["shared"]["wi"], "so": moe["shared"]["wo"]}
        if "mixer" in b:
            m = b["mixer"]
            e.update(in_proj=m["in_proj"], conv_w=m["conv_w"],
                     conv_b=m["conv_b"], A_log=m["A_log"], D=m["D"],
                     dt_bias=m["dt_bias"], mnorm=m["norm"]["scale"],
                     out_proj=m["out_proj"])
        else:
            a = b["attn"]
            e.update(wq=a["wq"], wk=a["wk"], wv=a["wv"], wao=a["wo"])
        period.append(e)
    return {"embed": tree["embed"], "final_norm": tree["final_norm"]["scale"],
            "period": period}


# -- weight rules ---------------------------------------------------------

# The embedding, at the dense cells' 0.02 over the embedding multiplier
# (12), so the residual stream starts at their scale. At 0.02 the tied
# head scores the current token 12 |e|^2 ~ 20 above the rest, ~17 times
# their spread at d 4096: the random model only repeats its input, and
# the check reads a gap of 0 in any precision, fp8 too.
EMBED_STD = 0.02 / 12


def _embed(key, path, shape):
    return jax.random.normal(key, shape, jnp.float32) * EMBED_STD



def _normal_fan_in(key, path, shape):
    """A matrix at fan_in ** -0.5 (inputs on the second-to-last axis)."""
    return jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5


def _conv(key, path, shape):
    """PyTorch's Conv1d default for a depthwise conv of width 4 (one input
    channel per group): weights and bias uniform within 1/sqrt(4)."""
    return jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)


def _a_log(key, path, shape):
    """Mamba-2's A = -exp(A_log), A uniform in [1, 16]."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))


def _dt_bias(key, path, shape):
    """Mamba-2's dt init: softplus(dt_bias) log-uniform in [1e-3, 0.1]."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    jnp.log(1e-3), jnp.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def _skip(key, path, shape):
    """D, the skip of x into y: around 1, spread so the check sees it."""
    return jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5)


WEIGHT_RULES = {"embed": _embed, "in_proj": _normal_fan_in,
                "out_proj": _normal_fan_in, "router": _normal_fan_in,
                "conv_w": _conv, "conv_b": _conv, "A_log": _a_log,
                "dt_bias": _dt_bias, "D": _skip}


# -- forward --------------------------------------------------------------


def _q8(x, axis, mode):
    """Round to ``mode`` (int8, or fp8 e4m3) along ``axis``, scaled by the
    absolute maximum there; ``f32`` leaves ``x`` as it is."""
    if mode == "f32":
        return x
    top = 127.0 if mode == "int8" else 448.0
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    s = jnp.where(s > 0, s, 1.0)
    if mode == "int8":
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _lin(x, w, mode):
    """x (S, K) @ w (K, ...) -> (S, ...), float32."""
    w = w.astype(jnp.float32)
    x = _q8(x, -1, mode)
    w = _q8(w.reshape(w.shape[0], -1), 0, mode).reshape(w.shape)
    return jnp.tensordot(x, w, axes=1, precision=HI)


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _swiglu(x, wg, wi, wo, mode):
    return _lin(jax.nn.silu(_lin(x, wg, mode)) * _lin(x, wi, mode), wo, mode)


def _mamba(spec, mode, h, w):
    S = h.shape[0]
    di, gn, H, P = (spec.d_inner, spec.groups * spec.d_state, spec.m_heads,
                    spec.m_head_dim)
    zx = _lin(h, w["in_proj"], mode)
    z, xbc, dt = zx[:, :di], zx[:, di:2 * di + 2 * gn], zx[:, 2 * di + 2 * gn:]
    # causal depthwise conv: tap i reads the token (K - 1 - i) back
    K = spec.conv
    xp = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    cw = w["conv_w"].astype(jnp.float32)
    xbc = jax.nn.silu(w["conv_b"].astype(jnp.float32)
                      + sum(cw[i] * xp[i:i + S] for i in range(K)))
    xs = xbc[:, :di].reshape(S, H, P)
    rep = H // spec.groups
    B = jnp.repeat(xbc[:, di:di + gn].reshape(S, spec.groups, -1), rep, 1)
    C = jnp.repeat(xbc[:, di + gn:].reshape(S, spec.groups, -1), rep, 1)
    dt = jax.nn.softplus(dt + w["dt_bias"].astype(jnp.float32))     # (S, H)
    A = -jnp.exp(w["A_log"].astype(jnp.float32))
    D = w["D"].astype(jnp.float32)

    def token(state, inp):                          # state (H, P, N)
        x_t, b_t, c_t, dt_t = inp
        state = (jnp.exp(dt_t * A)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], -1) + D[:, None] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((H, P, spec.d_state), jnp.float32),
                        (xs, B, C, dt))
    g = _norm(y.reshape(S, di) * jax.nn.silu(z), w["mnorm"], spec.eps)
    return _lin(g, w["out_proj"], mode)


def _attention(spec, mode, h, w):
    """Causal NoPE GQA attention, one block of queries at a time."""
    S = h.shape[0]
    H, KV, D = spec.heads, spec.kv_heads, spec.head_dim
    G = H // KV
    q = _q8(_lin(h, w["wq"], mode).reshape(S, H, D), -1, mode)
    k = _q8(_lin(h, w["wk"], mode).reshape(S, KV, D), -1, mode)
    v = _q8(_lin(h, w["wv"], mode).reshape(S, KV, D), 0, mode)
    qc = min(Q_CHUNK, S)
    qb = q.reshape(S // qc, qc, KV, G, D)
    kpos = jnp.arange(S)

    def block(args):
        i, qi = args
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k, precision=HI) * spec.attn_mult
        qpos = i * qc + jnp.arange(qc)
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -jnp.inf)
        p = _q8(jax.nn.softmax(s, axis=-1), -1, mode)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HI)

    o = jax.lax.map(block, (jnp.arange(S // qc), qb)).reshape(S, H * D)
    return _lin(o, w["wao"].reshape(H * D, spec.d), mode)


def _routed(spec, mode, h, w):
    logits = _lin(h, w["router"], mode)                              # (S, E)
    top, idx = jax.lax.top_k(logits, spec.top_k)
    gate = jax.nn.softmax(top, -1)
    mine = (idx - spec.first)[..., None] == jnp.arange(spec.held)
    weight = jnp.sum(gate[..., None] * mine, 1)                 # (S, held)

    def expert(acc, e):
        wg, wi, wo, we = e
        return acc + we[:, None] * _swiglu(h, wg, wi, wo, mode), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                          (w["wg"], w["wi"], w["wo"], weight.T))
    return out + _swiglu(h, w["sg"], w["si"], w["so"], mode)


@functools.partial(jax.jit, static_argnames=("spec", "mode"))
def logits_at(spec: Spec, w: dict, tokens, positions, mode: str = "f32"):
    """Float32 logits (n, vocab) at ``positions`` (n,) of a forward over
    ``tokens`` (S,). S is a multiple of Q_CHUNK or below it; causal
    masking and the recurrence keep padding after the last real token out
    of every real position."""
    with jax.default_matmul_precision("highest"):
        x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32) \
            * spec.emb_mult
        period = len(w["period"])
        for i, kind in enumerate(spec.layer_types):
            lw = jax.tree.map(lambda a, s=i // period: a[s],
                              w["period"][i % period])
            h = _norm(x, lw["ln1"], spec.eps)
            mix = _mamba if kind == "mamba" else _attention
            x = x + spec.res_mult * mix(spec, mode, h, lw)
            h = _norm(x, lw["ln2"], spec.eps)
            x = x + spec.res_mult * _routed(spec, mode, h, lw)
        x = _norm(x, w["final_norm"], spec.eps)
        x = jnp.take(x, positions, axis=0)
        return _lin(x, w["embed"].T[:, :spec.vocab], mode) \
            / spec.logits_scaling
