"""Fused flash-MHA kernel with on-the-fly K^T — Voltra C3 (PDMA) on TPU.

The paper's Fig. 4 insight: keep the whole per-tile MHA chain
(S = Q K^T -> online softmax -> O = P V) resident in fast memory, with
K^T performed on the fly by the weight streamer's transposer instead of a
dedicated transpose pass. The TPU analogue keeps the chain in VMEM:

  * grid = (batch*kv_heads, Sq/bq, Sk/bk), K/V axis innermost;
  * K arrives in its natural (bk, d) layout and is transposed inside the
    kernel (`jnp.dot(q, k.T)`) — never materialized transposed in HBM;
  * running max / denominator / output accumulator live in VMEM scratch
    across the KV sweep (online softmax), so the (Sq, Sk) score matrix
    never exists outside VMEM tiles — the PDMA-style residency;
  * GQA: the q-head group of each kv head is folded into the q rows, so
    grouped heads share the streamed K/V blocks (the chip's data-reuse
    argument, applied to the KV stream).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _mha_kernel(valid_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                acc_ref, *, n_kv: int, bq: int, bk: int, scale: float,
                causal: bool, group: int):
    kv = pl.program_id(2)

    @pl.when(kv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                       # (bq, d) — bq = group * q_rows
    k = k_ref[0]                       # (bk, d) — natural layout
    v = v_ref[0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    kv_pos = kv * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    # per-sequence valid length arrives as scalar-prefetched data (page-
    # aware kv_valid), so one compiled kernel serves every prompt length
    # in a bucket
    mask = kv_pos < valid_ref[pl.program_id(0)]
    if causal:
        # q rows are (group, rows) flattened; absolute position of row r
        # is (r % (bq//group)) + query block offset
        rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        q_pos = pl.program_id(1) * (bq // group) + rows % (bq // group)
        mask = mask & (q_pos >= kv_pos)
    s = jnp.where(mask, s, _NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(-1))
    # fully-masked rows/blocks must contribute zero probability (exp of
    # (-1e30) - (-1e30) would otherwise be 1)
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(-1)
    acc_ref[...] = (acc_ref[...] * corr[:, None]
                    + jnp.dot(p.astype(v.dtype), v,
                              preferred_element_type=jnp.float32))
    m_ref[...] = m_new

    @pl.when(kv == n_kv - 1)
    def _fin():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def mha(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
        bq: int = 128, bk: int = 128, kv_valid=None,
        interpret: bool = True) -> jax.Array:
    """q: (B, Sq, H, D); k, v: (B, Sk, KV, D) with H % KV == 0.

    Returns (B, Sq, H, D). The (Sq, Sk) score matrix is never materialized
    outside VMEM tiles.

    kv_valid: None (all Sk positions real), an int, or a (B,) int32 array —
    only the first kv_valid[b] kv positions of sequence b attend. It is a
    TRACED operand (streamed into the kernel per batch row), never a trace
    constant, so one compiled kernel serves every valid-length in a padded
    batch — the same bucket-stability contract the serving engine's
    bucketed prefill relies on (serving's jnp path lives in
    models/layers.flash_attention; this Pallas kernel is the TPU analogue
    reached via kernels/ops.attention).
    """
    B = q.shape[0]
    Sk = k.shape[1]
    if kv_valid is None:
        kv_valid = Sk
    kv_valid = jnp.broadcast_to(
        jnp.asarray(kv_valid, jnp.int32), (B,))
    return _mha(q, k, v, kv_valid, causal=causal, bq=bq, bk=bk,
                interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "causal", "bq", "bk", "interpret"))
def _mha(q: jax.Array, k: jax.Array, v: jax.Array, kv_valid: jax.Array, *,
         causal: bool, bq: int, bk: int, interpret: bool) -> jax.Array:
    B, Sq, H, D = q.shape
    _, Sk, KV, _ = k.shape
    assert H % KV == 0
    G = H // KV
    scale = D ** -0.5

    # fold (kv_head, group) into the batch/q-row axes so grouped heads
    # share each streamed K/V block. Row layout inside a q block is
    # (group, seq_row): block i holds seq rows [i*bq0, (i+1)*bq0) for all
    # G groups — the causal mask in the kernel relies on this.
    bq0 = min(bq, Sq)
    pq = (-Sq) % bq0
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0))) if pq else q
    Sqp = Sq + pq
    nq = Sqp // bq0
    qf = (qp.reshape(B, nq, bq0, KV, G, D).transpose(0, 3, 1, 4, 2, 5)
          .reshape(B * KV, nq * G * bq0, D))
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, Sk, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, Sk, D)

    bq_eff = bq0 * G                    # whole group shares each q block
    pk = (-Sk) % bk
    if pk:
        kf = jnp.pad(kf, ((0, 0), (0, pk), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pk), (0, 0)))
    Skp = Sk + pk
    n_kv = Skp // bk

    validf = jnp.repeat(kv_valid, KV)   # (B*KV,) — one row per b/kv program

    # valid lengths ride in as a scalar-prefetch operand (SMEM), as the
    # paged kernel's block table does: Mosaic refuses a rank-1 VMEM block
    # that is not a multiple of the 128-lane tiling
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * KV, nq, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq_eff, D), lambda b, i, j, vl: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j, vl: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j, vl: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq_eff, D),
                               lambda b, i, j, vl: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq_eff,), jnp.float32),
            pltpu.VMEM((bq_eff,), jnp.float32),
            pltpu.VMEM((bq_eff, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _mha_kernel, n_kv=n_kv, bq=bq_eff, bk=bk, scale=scale,
            causal=causal, group=G),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * KV, nq * G * bq0, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(validf, qf, kf, vf)

    out = (out.reshape(B, KV, nq, G, bq0, D).transpose(0, 2, 4, 1, 3, 5)
           .reshape(B, Sqp, KV, G, D))
    return out[:, :Sq].reshape(B, Sq, H, D)
