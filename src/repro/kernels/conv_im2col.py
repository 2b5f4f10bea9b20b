"""Implicit-im2col Conv2D kernel — the 6-D AGU analogue on TPU.

The chip's input streamer walks a programmable 6-D affine address pattern
so Conv2D never materializes an im2col buffer. The TPU analogue: the
kernel itself computes the strided window addresses (the AGU role) and
reads them with strided in-VMEM slices — one output row per grid step,
accumulating over the R x S filter taps:

    for (kh, kw):  out[oh, :, :] += x[oh*st + kh, kw::st, :] @ w[kh, kw]

Grid = (N, OH, COUT/bn); the (R, S) loop is unrolled inside the kernel
(static), so each tap is one MXU matmul of an (OW, C) strided window
against a (C, bn) filter slice — implicit im2col, no gather buffers.

Note on residency: each grid step maps one padded input image (1, Hp, Wp,
C) into VMEM. That is the right shape for the small feature maps of the
deep layers this kernel targets; a production variant would add an OH-
strip BlockSpec for the large early layers (recorded in DESIGN.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _conv_kernel(x_ref, w_ref, o_ref, *, R: int, S: int, stride: int,
                 OW: int):
    oh = pl.program_id(1)
    x = x_ref[0]                                  # (Hp, Wp, C)
    acc = jnp.zeros(o_ref.shape[2:], jnp.float32)  # (OW, bn)
    for kh in range(R):
        row = jax.lax.dynamic_index_in_dim(
            x, oh * stride + kh, axis=0, keepdims=False)   # (Wp, C)
        for kw in range(S):
            # strided window: input cols kw, kw+st, ... for all OW outputs
            win = jax.lax.slice(row, (kw, 0),
                                (kw + stride * (OW - 1) + 1, row.shape[1]),
                                (stride, 1))               # (OW, C)
            acc += jnp.dot(win, w_ref[kh, kw],
                           preferred_element_type=jnp.float32)
    o_ref[0, 0] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("stride", "bn", "interpret"))
def conv2d(x: jax.Array, w: jax.Array, *, stride: int = 1, bn: int = 128,
           interpret: bool = True) -> jax.Array:
    """x: (N, H, W, C); w: (R, S, C, K). SAME padding. -> (N, HO, WO, K)."""
    N, H, W, C = x.shape
    R, S, _, K = w.shape
    OH, OW = -(-H // stride), -(-W // stride)
    # SAME padding (as lax.conv computes it)
    ph = max((OH - 1) * stride + R - H, 0)
    pw = max((OW - 1) * stride + S - W, 0)
    xp = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                     (pw // 2, pw - pw // 2), (0, 0)))
    bn = min(bn, K)
    pk = (-K) % bn
    wp = jnp.pad(w, ((0, 0), (0, 0), (0, 0), (0, pk))) if pk else w
    Kp = K + pk
    Hp, Wp = xp.shape[1], xp.shape[2]

    out = pl.pallas_call(
        functools.partial(_conv_kernel, R=R, S=S, stride=stride, OW=OW),
        grid=(N, OH, Kp // bn),
        in_specs=[
            pl.BlockSpec((1, Hp, Wp, C), lambda n, oh, j: (n, 0, 0, 0)),
            pl.BlockSpec((R, S, C, bn), lambda n, oh, j: (0, 0, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, OW, bn),
                               lambda n, oh, j: (n, oh, 0, j)),
        out_shape=jax.ShapeDtypeStruct((N, OH, OW, Kp), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(xp, wp)
    return out[..., :K]
