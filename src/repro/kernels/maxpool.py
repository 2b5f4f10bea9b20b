"""Maxpool unit — Sec. II-E on TPU.

The chip's maxpool module has eight parallel comparison lanes and handles
arbitrary window sizes sequentially. The TPU analogue: grid over output
rows, lanes = the channel vector, the (R x S) window reduced by a static
sequential max loop inside the kernel — same structure, lane-width 128
instead of 8 (hardware adaptation)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _maxpool_kernel(x_ref, o_ref, *, R: int, S: int, stride: int, OW: int):
    oh = pl.program_id(1)
    x = x_ref[0]                                   # (Hp, Wp, C)
    out = jnp.full(o_ref.shape[2:], -jnp.inf, jnp.float32)
    for kh in range(R):                            # sequential window walk
        row = jax.lax.dynamic_index_in_dim(x, oh * stride + kh, 0, False)
        for kw in range(S):
            win = jax.lax.slice(row, (kw, 0),
                                (kw + stride * (OW - 1) + 1, row.shape[1]),
                                (stride, 1))       # (OW, C)
            out = jnp.maximum(out, win.astype(jnp.float32))
    o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "stride",
                                             "interpret"))
def maxpool2d(x: jax.Array, *, window: int = 2, stride: int = 2,
              interpret: bool = True) -> jax.Array:
    """x: (N, H, W, C), VALID padding -> (N, OH, OW, C)."""
    N, H, W, C = x.shape
    R = S = window
    OH = (H - R) // stride + 1
    OW = (W - S) // stride + 1
    return pl.pallas_call(
        functools.partial(_maxpool_kernel, R=R, S=S, stride=stride, OW=OW),
        grid=(N, OH),
        in_specs=[pl.BlockSpec((1, H, W, C), lambda n, oh: (n, 0, 0, 0))],
        out_specs=pl.BlockSpec((1, 1, OW, C), lambda n, oh: (n, oh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, OH, OW, C), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x)


def maxpool2d_ref(x: jax.Array, *, window: int = 2, stride: int = 2
                  ) -> jax.Array:
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max,
        (1, window, window, 1), (1, stride, stride, 1), "VALID")
