"""Grouped SwiGLU over the routed experts a chip holds, as two Pallas calls.

The routed rows arrive sorted by expert: ``group_sizes[e]`` rows for held
expert ``e``, one after another, then rows that no held expert takes
(routed to experts on other chips) to the end. Each call walks only the
row tiles that some held expert's rows touch (megablox's group metadata:
a tile two experts share is visited once per expert, and each visit
stores only its own expert's rows), so the work follows the rows that
were routed here and not the static row count:

  _experts_up    h = silu(x @ wg[e]) * (x @ wi[e])      (rows, f)
  _experts_down  y = h @ wo[e]                          (rows, d)

Rows past the held experts' rows are zero in ``y``. The two jitted
wrappers name the kernels in a device trace (``_experts_up.N``,
``_experts_down.N``). Under autodiff the backward pass is that of the
same products by ``jax.lax.ragged_dot``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata


def _tile(n: int, cap: int) -> int:
    """The largest lane tile of at most ``cap`` that divides ``n`` (``n``
    itself where it is below ``cap`` or no multiple of 128 divides it)."""
    if n <= cap:
        return n
    for t in range(cap - cap % 128, 0, -128):
        if n % t == 0:
            return t
    return n


def row_tile(m: int) -> int:
    """Rows per tile: small for a decode step, whose held experts see a
    few rows each, larger for a prefill chunk."""
    return 32 if m <= 1024 else 128


def _kernel(offsets, gids, mtiles, x_ref, *refs, tm: int, nk: int,
            gated: bool):
    # refs: the weights (1 or 2), the output, an accumulator per weight
    n_w = 2 if gated else 1
    w_refs, o_ref, acc = refs[:n_w], refs[n_w], refs[n_w + 1:]
    t, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _():
        for a in acc:
            a[...] = jnp.zeros_like(a)

    x = x_ref[...]
    for a, w in zip(acc, w_refs):
        a[...] += jnp.dot(x, w[...], preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _():
        g = gids[t]
        row = mtiles[t] * tm + jax.lax.broadcasted_iota(
            jnp.int32, o_ref.shape, 0)
        mine = (row >= offsets[g]) & (row < offsets[g + 1])
        v = jax.nn.silu(acc[0][...]) * acc[1][...] if gated else acc[0][...]
        # a tile two experts share: keep the other expert's rows
        o_ref[...] = jnp.where(mine, v, o_ref[...].astype(jnp.float32)
                               ).astype(o_ref.dtype)


def _grouped(x, ws, meta, n_tiles, *, interpret: bool):
    """(m, k) rows x ``ws`` (each (E, k, n)) -> (m, n), ``gated`` when two
    weights are given."""
    m, kd = x.shape
    n = ws[0].shape[2]
    tm, tk, tn = row_tile(m), _tile(kd, 512), _tile(n, 1024)
    nk = kd // tk
    gated = len(ws) == 2

    def x_map(j, t, k, offsets, gids, mtiles):
        return mtiles[t], k

    def w_map(j, t, k, offsets, gids, mtiles):
        return gids[t], k, j

    def o_map(j, t, k, offsets, gids, mtiles):
        return mtiles[t], j

    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, nk=nk, gated=gated),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, n_tiles, nk),
            in_specs=[pl.BlockSpec((tm, tk), x_map)]
            + [pl.BlockSpec((None, tk, tn), w_map) for _ in ws],
            out_specs=pl.BlockSpec((tm, tn), o_map),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32) for _ in ws]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(*meta, x, *ws)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _experts_up(x, wg, wi, meta, n_tiles, *, interpret: bool):
    return _grouped(x, (wg, wi), meta, n_tiles, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _experts_down(h, wo, meta, n_tiles, *, interpret: bool):
    return _grouped(h, (wo,), meta, n_tiles, interpret=interpret)


def _metadata(group_sizes, m: int):
    meta, n_tiles = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=row_tile(m),
        start_group=jnp.int32(0),
        num_nonzero_groups=group_sizes.shape[0], visit_empty_groups=False)
    return meta, n_tiles


def _held_rows(group_sizes, m: int):
    return (jnp.arange(m) < group_sizes.sum())[:, None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def swiglu_experts(x, wg, wi, wo, group_sizes, interpret: bool = False):
    """Rows ``x`` (m, d), sorted by held expert, through their expert's
    SwiGLU: (m, d), zero past the held experts' rows. ``m`` is a multiple
    of ``row_tile(m)``."""
    meta, n_tiles = _metadata(group_sizes, x.shape[0])
    h = _experts_up(x, wg, wi, meta, n_tiles, interpret=interpret)
    y = _experts_down(h, wo, meta, n_tiles, interpret=interpret)
    # rows past the held experts' rows are never visited
    return jnp.where(_held_rows(group_sizes, x.shape[0]), y, 0)


def swiglu_experts_ragged(x, wg, wi, wo, group_sizes):
    """The same products by ``jax.lax.ragged_dot`` (no kernel)."""
    f32 = jnp.float32
    g = jax.lax.ragged_dot(x, wg, group_sizes, preferred_element_type=f32)
    u = jax.lax.ragged_dot(x, wi, group_sizes, preferred_element_type=f32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    y = jax.lax.ragged_dot(h, wo, group_sizes, preferred_element_type=f32)
    return jnp.where(_held_rows(group_sizes, x.shape[0]), y, 0
                     ).astype(x.dtype)


def _fwd(x, wg, wi, wo, group_sizes, interpret):
    return (swiglu_experts(x, wg, wi, wo, group_sizes, interpret),
            (x, wg, wi, wo, group_sizes))


def _bwd(interpret, res, g):
    x, wg, wi, wo, group_sizes = res
    _, vjp = jax.vjp(
        lambda *a: swiglu_experts_ragged(*a, group_sizes), x, wg, wi, wo)
    return (*vjp(g), None)


swiglu_experts.defvjp(_fwd, _bwd)
