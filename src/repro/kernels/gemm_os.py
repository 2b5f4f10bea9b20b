"""3D-blocked output-stationary GeMM kernel — Voltra C1 + C4 on TPU.

The TPU realization of the paper's 3D spatial array:

  * the (bm, bn, bk) BlockSpec tiling is the balanced 3-axis unrolling —
    grid = (M/bm, N/bn, K/bk) with the K axis innermost/sequential;
  * output-stationarity: the fp32/int32 accumulator tile lives in VMEM
    scratch for the whole K sweep (the array's accumulation registers) and
    is written out exactly once — high-precision partial sums never touch
    HBM, just as the chip never spills them to the shared memory;
  * the quantization SIMD unit (C4) is the fused epilogue: on the last K
    step the accumulator is scaled/clipped/rounded to INT8 while still in
    VMEM — no second pass over the output in HBM;
  * mixed-grained prefetching (C2) maps onto the Pallas grid pipeline:
    the next (x, w) blocks stream HBM->VMEM while the MXU consumes the
    current ones (a depth-2 hardware FIFO per operand).

Hardware adaptation (DESIGN.md): the chip unrolls 8x8x8; the MXU wants
128-multiples, so default blocks are (128, 128, 128)-class and tile-edge
utilization math happens at that granularity instead.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gemm_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int,
                 quant_scale: Optional[float]):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=acc_ref.dtype)

    @pl.when(k == n_k - 1)
    def _epilogue():
        acc = acc_ref[...]
        if quant_scale is not None:
            # fused quantization SIMD: scale -> round -> clip -> int8,
            # performed on the VMEM-resident accumulator tile
            q = jnp.round(acc.astype(jnp.float32) * quant_scale)
            o_ref[...] = jnp.clip(q, -128, 127).astype(o_ref.dtype)
        else:
            o_ref[...] = acc.astype(o_ref.dtype)


def _pad_to(x: jax.Array, mults: Tuple[int, ...]) -> jax.Array:
    pads = [(0, (-d) % m) for d, m in zip(x.shape, mults)]
    if any(p for _, p in pads):
        return jnp.pad(x, pads)
    return x


@functools.partial(
    jax.jit,
    static_argnames=("block", "out_dtype", "quant_scale", "interpret"))
def gemm_os(x: jax.Array, w: jax.Array, *,
            block: Tuple[int, int, int] = (128, 128, 128),
            out_dtype=None,
            quant_scale: Optional[float] = None,
            interpret: bool = True) -> jax.Array:
    """out[M, N] = x[M, K] @ w[K, N], output-stationary over K blocks.

    INT8 inputs accumulate in INT32 (the chip's datapath); float inputs in
    FP32. ``quant_scale`` enables the fused INT8 epilogue (out_dtype is
    then int8). Shapes are padded up to block multiples (the spatial-
    utilization edge effect — the padding fraction IS (1 - spatial util)).
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    bm, bn, bk = block
    integer = jnp.issubdtype(x.dtype, jnp.integer)
    acc_dtype = jnp.int32 if integer else jnp.float32
    if out_dtype is None:
        out_dtype = jnp.int8 if quant_scale is not None else (
            jnp.int32 if integer else x.dtype)

    xp = _pad_to(x, (bm, bk))
    wp = _pad_to(w, (bk, bn))
    Mp, Kp = xp.shape
    _, Np = wp.shape
    n_k = Kp // bk

    out = pl.pallas_call(
        functools.partial(_gemm_kernel, n_k=n_k, quant_scale=quant_scale),
        grid=(Mp // bm, Np // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xp, wp)
    return out[:M, :N]


def spatial_utilization(M: int, K: int, N: int,
                        block: Tuple[int, int, int] = (128, 128, 128)
                        ) -> float:
    """Tile-edge efficiency of the 3D blocking — the same formula as the
    chip's spatial utilization (core/spatial.py), at MXU granularity."""
    bm, bn, bk = block

    def eff(d, b):
        return d / (b * -(-d // b))

    return eff(M, bm) * eff(N, bn) * eff(K, bk)
