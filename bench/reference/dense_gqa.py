"""Plain float32 forward of a dense GQA decoder, for the correctness check.

Straight ``jax.numpy``, no kernels, no cache, no batching; it imports
nothing of the program. One call runs one whole sequence and returns
the logits at the positions asked for.

  embed -> L x [x += Attn(RMSNorm(x)); x += SwiGLU(RMSNorm(x))]
        -> RMSNorm -> logits (tied: embed^T; untied: head)

RMSNorm: x * rsqrt(mean(x^2) + eps) * scale. Attention: q, k, v
projections with optional bias, rotary embedding on q and k (halves
rotated, base ``rope_theta``), causal softmax(q k^T / sqrt(head_dim)),
query head h reading key/value head h // (heads / kv_heads). SwiGLU:
silu(x Wg) * (x Wi), then Wd.

Departures from the published models, shared with the program: Granite's
embedding, attention and residual multipliers and its logits scaling are
absent (plain 1/sqrt(head_dim), no scaling), and every norm uses the
configured ``rms_norm_eps`` as the program runs it.

``mode="int8"`` or ``"fp8"`` is the control: the same forward with both
inputs of every matrix product rounded to int8, or to fp8 (e4m3), each
scaled by its absolute maximum (activations per row, weights per output
channel, attention operands per row): the precision one step below the
bfloat16 the models are served in.

``plain`` gives the program's parameter tree under this module's names;
``counts`` gives the operations and bytes the dense stack needs, from
the configuration's shapes alone (the same count whatever implements
the work): multiply-adds as 2 operations, weights and KV in bfloat16.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Iterable

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
Q_CHUNK = 512
BF16 = 2


@dataclasses.dataclass(frozen=True)
class Spec:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    eps: float
    tied: bool

    @classmethod
    def from_model(cls, m: dict) -> "Spec":
        """From a configuration file's ``model`` object (published keys)."""
        return cls(layers=m["num_hidden_layers"], d=m["hidden_size"],
                   heads=m["num_attention_heads"],
                   kv_heads=m["num_key_value_heads"],
                   head_dim=m.get("head_dim") or
                   m["hidden_size"] // m["num_attention_heads"],
                   d_ff=m["intermediate_size"], vocab=m["vocab_size"],
                   rope_theta=float(m["rope_theta"]),
                   eps=float(m["rms_norm_eps"]),
                   tied=bool(m["tie_word_embeddings"]))

    @property
    def layer_matmul_params(self) -> int:
        """Weights one token multiplies per layer: q, k, v, o and the
        three SwiGLU matrices."""
        hd = self.head_dim
        return (self.d * (self.heads + 2 * self.kv_heads) * hd
                + self.heads * hd * self.d + 3 * self.d * self.d_ff)

    @property
    def kv_bytes_per_token(self) -> int:
        """Keys and values of one token over every layer."""
        return 2 * self.layers * self.kv_heads * self.head_dim * BF16

    def token_flops(self, context: int, logits: bool) -> float:
        """One token through the stack, attending to ``context`` keys
        (itself included); ``logits`` adds the output head."""
        f = 2 * self.layers * self.layer_matmul_params
        # q k^T and p v
        f += 4 * self.layers * self.heads * self.head_dim * context
        if logits:
            f += 2 * self.d * self.vocab
        return float(f)

    def prefill_flops(self, n: int) -> float:
        """A prompt of ``n`` tokens, causal, logits at the last one only."""
        f = 2 * self.layers * self.layer_matmul_params * n
        f += 4 * self.layers * self.heads * self.head_dim * n * (n + 1) / 2
        return float(f + 2 * self.d * self.vocab)

    def decode_flops(self, contexts: Iterable[int]) -> float:
        """One decode step: one token per live request, each at its
        context."""
        return sum(self.token_flops(c, True) for c in contexts)

    def paged_attn_bytes(self, contexts: Iterable[int]) -> float:
        """Decode attention over every layer for one step: each live
        request's keys and values read once, its query read and output
        written (bfloat16)."""
        qo = 2 * self.heads * self.head_dim * BF16
        return float(sum(c * self.kv_bytes_per_token + self.layers * qo
                         for c in contexts))

    def paged_attn_flops(self, contexts: Iterable[int]) -> float:
        return float(sum(4 * self.layers * self.heads * self.head_dim * c
                         for c in contexts))


def counts(model: dict) -> Spec:
    """The counts of a configuration file's ``model`` object: its
    ``Spec``, whose methods count."""
    return Spec.from_model(model)


def plain(tree) -> dict:
    """The program's parameter tree under this module's flat names
    (layer-stacked, layer axis first). Raises if the program's layout is
    not the dense GQA stack this module computes."""
    blk = tree["blocks"]
    if blk["tail"] or set(blk["scan"]) != {"0"}:
        raise ValueError("the reference computes one homogeneous stack")
    b = blk["scan"]["0"]
    a, m = b["attn"], b["mlp"]
    w = {"ln1": b["ln1"]["scale"], "ln2": b["ln2"]["scale"],
         "wq": a["wq"], "wk": a["wk"], "wv": a["wv"], "wo": a["wo"],
         "wi": m["wi"], "wg": m["wg"], "wd": m["wo"]}
    for k in ("bq", "bk", "bv"):
        if k in a:
            w[k] = a[k]
    out = {"embed": tree["embed"], "final_norm": tree["final_norm"]["scale"],
           "layers": w}
    if "head" in tree:
        out["head"] = tree["head"]
    return out


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


def _rope(x, pos, theta):
    """x (S, H, D); halves rotated."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-jnp.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attend(spec, q, k, v, mode):
    """Causal GQA attention, one block of queries at a time."""
    S = q.shape[0]
    G = spec.heads // spec.kv_heads
    q, k, v = _q8(q, -1, mode), _q8(k, -1, mode), _q8(v, 0, mode)
    qc = min(Q_CHUNK, S)
    qb = q.reshape(S // qc, qc, spec.kv_heads, G, spec.head_dim)
    kpos = jnp.arange(S)

    def block(args):
        i, qi = args
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k, precision=HI) \
            * spec.head_dim ** -0.5
        qpos = i * qc + jnp.arange(qc)
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -jnp.inf)
        p = _q8(jax.nn.softmax(s, axis=-1), -1, mode)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HI)

    o = jax.lax.map(block, (jnp.arange(S // qc), qb))
    return o.reshape(S, spec.heads * spec.head_dim)


def _layer(spec, mode, x, w):
    S = x.shape[0]
    H, KV, D = spec.heads, spec.kv_heads, spec.head_dim
    pos = jnp.arange(S)
    h = _norm(x, w["ln1"], spec.eps)
    q = _lin(h, w["wq"], mode)
    k = _lin(h, w["wk"], mode)
    v = _lin(h, w["wv"], mode)
    if "bq" in w:
        q = q + w["bq"].astype(jnp.float32)
        k = k + w["bk"].astype(jnp.float32)
        v = v + w["bv"].astype(jnp.float32)
    q = _rope(q.reshape(S, H, D), pos, spec.rope_theta)
    k = _rope(k.reshape(S, KV, D), pos, spec.rope_theta)
    o = _attend(spec, q, k, v.reshape(S, KV, D), mode)
    x = x + _lin(o, w["wo"].reshape(H * D, spec.d), mode)
    h = _norm(x, w["ln2"], spec.eps)
    f = jax.nn.silu(_lin(h, w["wg"], mode)) * _lin(h, w["wi"], mode)
    return x + _lin(f, w["wd"], mode), None


@functools.partial(jax.jit, static_argnames=("spec", "mode"))
def logits_at(spec: Spec, w: dict, tokens, positions, mode: str = "f32"):
    """Float32 logits (n, vocab) at ``positions`` (n,) of a forward over
    ``tokens`` (S,). S is a multiple of Q_CHUNK or below it; causal
    masking keeps padding after the last real token out of every real
    position."""
    with jax.default_matmul_precision("highest"):
        x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
        x, _ = jax.lax.scan(functools.partial(_layer, spec, mode), x,
                            w["layers"])
        x = _norm(x, w["final_norm"], spec.eps)
        x = jnp.take(x, positions, axis=0)
        head = w["embed"].T if spec.tied else w["head"]
        return _lin(x, head[:, :spec.vocab], mode)
