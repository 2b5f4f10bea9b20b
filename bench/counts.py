"""Operations and bytes the algorithm needs, from the configuration's
shapes alone: the same count whatever implements the work.

``Shapes`` reads a configuration file's ``model`` object. Counts are of
multiply-adds as 2 operations; weights and KV in bfloat16 (2 bytes).
"""
from __future__ import annotations

import dataclasses
from typing import Iterable

BF16 = 2


@dataclasses.dataclass(frozen=True)
class Shapes:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    @classmethod
    def from_model(cls, m: dict) -> "Shapes":
        return cls(layers=m["num_hidden_layers"], d=m["hidden_size"],
                   heads=m["num_attention_heads"],
                   kv_heads=m["num_key_value_heads"],
                   head_dim=m.get("head_dim") or
                   m["hidden_size"] // m["num_attention_heads"],
                   d_ff=m["intermediate_size"], vocab=m["vocab_size"])

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


def token_flops(s: Shapes, context: int, logits: bool) -> float:
    """One token through the stack, attending to ``context`` keys (itself
    included); ``logits`` adds the output head."""
    f = 2 * s.layers * s.layer_matmul_params
    f += 4 * s.layers * s.heads * s.head_dim * context     # q k^T and p v
    if logits:
        f += 2 * s.d * s.vocab
    return float(f)


def prefill_flops(s: Shapes, n: int) -> float:
    """A prompt of ``n`` tokens, causal, logits at the last one only."""
    f = 2 * s.layers * s.layer_matmul_params * n
    f += 4 * s.layers * s.heads * s.head_dim * n * (n + 1) / 2
    return float(f + 2 * s.d * s.vocab)


def decode_flops(s: Shapes, contexts: Iterable[int]) -> float:
    """One decode step: one token per live request, each at its context."""
    return sum(token_flops(s, c, True) for c in contexts)


def paged_attn_bytes(s: Shapes, contexts: Iterable[int]) -> float:
    """Decode attention over every layer for one step: each live
    request's keys and values read once, its query read and output
    written (bfloat16)."""
    contexts = list(contexts)
    qo = 2 * s.heads * s.head_dim * BF16
    return float(sum(c * s.kv_bytes_per_token + s.layers * qo
                     for c in contexts))


def paged_attn_flops(s: Shapes, contexts: Iterable[int]) -> float:
    return float(sum(4 * s.layers * s.heads * s.head_dim * c
                     for c in contexts))
