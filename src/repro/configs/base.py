"""Configuration system: model/shape/mesh/run configs + input_specs().

Every assigned architecture gets a ``ModelConfig`` in ``configs/<id>.py``.
Shapes are the four assigned input-shape cells; ``input_specs`` builds
ShapeDtypeStruct stand-ins (no allocation) for dry-runs.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Model config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 0
    top_k: int = 1
    # > 0: the token-dropping layer (layers.moe_apply), capacity per
    # group; 0: the dropless routed layer (layers.routed_apply) over the
    # experts this chip holds (ModelConfig.experts_first/_held)
    capacity_factor: float = 1.25
    moe_every: int = 1          # MoE FFN every Nth layer (1 = every layer)
    shared_expert: bool = False  # extra always-on expert (llama4 style)
    shared_d_ff: int = 0         # the shared expert's width (0 = d_ff)
    # GShard-style dispatch groups: capacity is enforced per group and the
    # group dim is sharded with the batch, so routing scatters stay local
    # to their data shard (EXPERIMENTS.md §Perf B6). 32 = pod*data of the
    # production mesh; groups fall back to 1 when tokens % groups != 0.
    dispatch_groups: int = 32


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    state_dim: int = 128        # N (dstate)
    head_dim: int = 64          # P  (d_inner = heads * head_dim)
    expand: int = 2
    conv_width: int = 4
    chunk: int = 256            # SSD chunk length
    num_groups: int = 1         # B/C groups (like GQA for SSM)


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """Griffin/RecurrentGemma style block pattern."""
    pattern: Tuple[str, ...] = ()   # e.g. ("rglru", "rglru", "local_attn")
    window: int = 2048              # local attention window
    lru_dim: int = 0                # RG-LRU recurrence width (0 = d_model)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | encdec | vlm | ssm | hybrid
    num_layers: int
    d_model: int
    num_heads: int
    kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0           # 0 -> d_model // num_heads
    qkv_bias: bool = False
    norm: str = "rmsnorm"       # rmsnorm | layernorm
    act: str = "silu"           # silu (gated) | gelu (plain)
    gated_ffn: bool = True
    rope_theta: float = 10_000.0
    tie_embeddings: bool = False
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    hybrid: Optional[HybridConfig] = None
    # enc-dec
    enc_layers: int = 0         # >0 -> encoder-decoder
    # vlm / audio frontend stubs
    frontend: Optional[str] = None   # "patch" (vlm) | "frames" (audio)
    frontend_tokens: int = 0         # tokens contributed by the frontend stub
    # numerics / memory policy
    dtype: str = "bfloat16"
    moment_dtype: str = "float32"    # optimizer moment dtype (bf16 for huge MoE)
    remat: str = "full"              # none | full | dots
    # decode attention KV chunk (online softmax over the cache). 0 = single
    # pass — the right choice when the cache seq axis is context-parallel
    # sharded (XLA partitions the einsum; a scan would serialize it).
    decode_kv_chunk: int = 2048
    # chunked (online-softmax scan) vs one-shot full-sequence attention.
    # One-shot is the right path under sequence/context parallelism where
    # the per-device q block is small (EXPERIMENTS.md §Perf A4).
    flash_chunking: bool = True
    # paged-decode attention implementation: "kernel" (Pallas flash-decode,
    # block-table gather inside the kernel — the default and the only path
    # that never materializes the gathered KV) | "gather" (PR-1 baseline:
    # dense pool[block_table] gather per layer, kept as the measured
    # anti-pattern in benchmarks/serve_bench.py). Dense-slot decode ignores
    # this.
    paged_attn_impl: str = "kernel"
    # KV-cache storage dtype: "bfloat16" | "int8". int8 halves decode
    # cache traffic + footprint (the chip's INT8 theme applied to the KV
    # cache); values are stored as round(x * 127 / kv_scale) with a
    # per-model static absmax bound (EXPERIMENTS.md §Perf C4).
    kv_cache_dtype: str = "bfloat16"
    kv_scale: float = 8.0
    # Granite's scalings; the defaults leave a model without them as it
    # was. The embedding is multiplied by embedding_multiplier, every
    # mixer and FFN branch by residual_multiplier before its residual
    # add, and the logits divided by logits_scaling; attention_multiplier
    # is the softmax scale (0 = 1/sqrt(head_dim)); norm_eps is every
    # RMSNorm's epsilon.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    logits_scaling: float = 1.0
    norm_eps: float = 1e-6
    # "rope" (rotary q and k) | "nope" (no positional embedding)
    position_embedding: str = "rope"
    # the routed experts this chip holds of each dropless routed layer:
    # experts_first .. experts_first + experts_held - 1 (0 held = all).
    # The router still scores every expert; rows routed elsewhere add
    # nothing here (one chip's share of an expert-parallel deployment).
    experts_first: int = 0
    experts_held: int = 0
    # notes (arch-applicability etc.)
    note: str = ""

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def held_experts(self) -> Tuple[int, int]:
        """(first, count) of the routed experts held here."""
        return (self.experts_first,
                self.experts_held or self.moe.num_experts - self.experts_first)

    @property
    def is_encdec(self) -> bool:
        return self.enc_layers > 0

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def subquadratic(self) -> bool:
        """True when the arch supports ~500k-token decode (no full-attn cache)."""
        return self.family in ("ssm", "hybrid")

    def _attn_params(self) -> int:
        d, hd = self.d_model, self.resolved_head_dim
        attn = (d * self.num_heads * hd + 2 * d * self.kv_heads * hd
                + self.num_heads * hd * d)
        if self.qkv_bias:
            attn += (self.num_heads + 2 * self.kv_heads) * hd
        return attn

    def _ffn_params(self, d_ff: int) -> int:
        return (3 if self.gated_ffn else 2) * self.d_model * d_ff

    def _ssm_params(self) -> int:
        """One Mamba-2 mixer: in/out projections, conv, A."""
        s, d = self.ssm, self.d_model
        d_inner = s.expand * d
        return (d * (2 * d_inner + 2 * s.num_groups * s.state_dim
                     + d_inner // s.head_dim)
                + d_inner * s.conv_width + d_inner * d
                + d_inner // s.head_dim)

    def _moe_params(self, active: bool) -> int:
        """One routed FFN: the experts (those a token runs when
        ``active``), the router and the shared expert."""
        moe = self.moe
        n = moe.top_k if active else moe.num_experts
        ffn = n * self._ffn_params(self.d_ff)
        if not active:
            ffn += self.d_model * moe.num_experts
        if moe.shared_expert:
            ffn += self._ffn_params(moe.shared_d_ff or self.d_ff)
        return ffn

    def _hybrid_params(self, active: bool) -> int:
        """Blocks of a hybrid pattern, layer by layer: the mixer of each
        kind, then its FFN (none after a bare ``ssm`` mixer)."""
        h = self.hybrid
        lru = h.lru_dim or self.d_model
        # RG-LRU block: in/out proj + gates (approx)
        lru_block = 2 * self.d_model * lru + 3 * lru * lru
        kinds = (h.pattern * self.num_layers)[: self.num_layers]
        total = 0
        for kind in kinds:
            if kind in ("ssm", "ssm_moe"):
                total += self._ssm_params()
            elif kind == "rglru":
                total += lru_block
            else:
                total += self._attn_params()
            if kind.endswith("_moe"):
                total += self._moe_params(active)
            elif kind != "ssm":
                total += self._ffn_params(self.d_ff)
        return total

    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks), used for 6ND."""
        attn = self._attn_params()
        ffn_dense = self._ffn_params(self.d_ff)
        total = 0
        if self.family == "ssm":
            total = self.num_layers * self._ssm_params()
        elif self.family == "hybrid":
            total = self._hybrid_params(active=False)
        else:
            moe = self.moe
            for layer in range(self.num_layers):
                is_moe = moe is not None and moe.num_experts > 0 and (layer % moe.moe_every == moe.moe_every - 1)
                if is_moe:
                    ffn = moe.num_experts * ffn_dense + self.d_model * moe.num_experts
                    if moe.shared_expert:
                        ffn += ffn_dense
                else:
                    ffn = ffn_dense
                total += attn + ffn
            if self.is_encdec:
                # encoder layers: self-attn + ffn; decoder layers already counted,
                # add cross-attention to each decoder layer
                total += self.enc_layers * (attn + ffn_dense)
                total += self.num_layers * attn  # cross-attn
        total += self.vocab * self.d_model * (1 if self.tie_embeddings else 2)
        return int(total)

    def active_param_count(self) -> int:
        """Per-token active params (= total for dense; routed subset for MoE)."""
        if self.moe is None or self.moe.num_experts == 0:
            return self.param_count()
        embed = self.vocab * self.d_model * (1 if self.tie_embeddings else 2)
        if self.family == "hybrid":
            return int(self._hybrid_params(active=True) + embed)
        attn = self._attn_params()
        ffn_dense = self._ffn_params(self.d_ff)
        moe = self.moe
        total = 0
        for layer in range(self.num_layers):
            is_moe = (layer % moe.moe_every == moe.moe_every - 1)
            if is_moe:
                ffn = moe.top_k * ffn_dense + (ffn_dense if moe.shared_expert else 0)
            else:
                ffn = ffn_dense
            total += attn + ffn
        return int(total + embed)


# ---------------------------------------------------------------------------
# Shapes (assigned cells)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str          # "train" | "prefill" | "decode"


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


def cell_runnable(cfg: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Whether (arch, shape) is a runnable dry-run cell; reason if not."""
    if shape.name == "long_500k" and not cfg.subquadratic:
        return False, "long_500k needs sub-quadratic attention; full-attn arch (see DESIGN.md)"
    return True, ""


# ---------------------------------------------------------------------------
# input_specs: ShapeDtypeStruct stand-ins (the dry-run contract)
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this cell.

    train:   {tokens, labels[, frontend_embeds]}         -> train_step
    prefill: {tokens[, frontend_embeds]}                  -> prefill_step
    decode:  {tokens(1 new), cache(kv/ssm state), pos}    -> serve_step
    """
    i32 = jnp.int32
    b, s = shape.global_batch, shape.seq_len
    dt = jnp.dtype(cfg.dtype)

    def tok(bb, ss):
        return jax.ShapeDtypeStruct((bb, ss), i32)

    specs: Dict[str, Any] = {}
    if shape.kind == "train":
        if cfg.is_encdec:
            specs["enc_embeds"] = jax.ShapeDtypeStruct((b, s, cfg.d_model), dt)
            specs["tokens"] = tok(b, s)
            specs["labels"] = tok(b, s)
        elif cfg.frontend == "patch":
            ft = cfg.frontend_tokens
            specs["frontend_embeds"] = jax.ShapeDtypeStruct((b, ft, cfg.d_model), dt)
            specs["tokens"] = tok(b, s - ft)
            specs["labels"] = tok(b, s - ft)
        else:
            specs["tokens"] = tok(b, s)
            specs["labels"] = tok(b, s)
    elif shape.kind == "prefill":
        if cfg.is_encdec:
            specs["enc_embeds"] = jax.ShapeDtypeStruct((b, s, cfg.d_model), dt)
            specs["tokens"] = tok(b, s)
        elif cfg.frontend == "patch":
            ft = cfg.frontend_tokens
            specs["frontend_embeds"] = jax.ShapeDtypeStruct((b, ft, cfg.d_model), dt)
            specs["tokens"] = tok(b, s - ft)
        else:
            specs["tokens"] = tok(b, s)
    else:  # decode: one new token against a cache of seq_len
        specs["tokens"] = tok(b, 1)
        specs["pos"] = jax.ShapeDtypeStruct((b,), i32)
        specs["cache"] = cache_specs(cfg, b, s)
    return specs


def cache_specs(cfg: ModelConfig, batch: int, seq_len: int) -> Any:
    """Decode-cache ShapeDtypeStructs (KV cache / SSM state / hybrid mix)."""
    from repro.models import api  # local import to avoid cycles
    return api.cache_shapes(cfg, batch, seq_len)
