"""GPipe-style pipeline parallelism over shard_map + ppermute.

Stages are laid out along a mesh axis; each device executes its stage's
params on a stream of microbatches and hands activations to the next
stage with ``lax.ppermute`` over a ring. Classic GPipe schedule: with M
microbatches and S stages, the loop runs M + S - 1 ticks and the bubble
fraction is (S-1)/(M+S-1). Bubble ticks execute the stage on don't-care
data (exactly what the hardware would do) — only valid outputs are
collected at the last stage.

This is the optional pipeline mode of the launcher (maps stages to the
"pod" axis in the multi-pod mesh); the dry-run proves it lowers and
compiles, tests/test_pipeline.py proves numerical equivalence to the
sequential stack on a forced-multi-device CPU.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map


def gpipe(stage_fn: Callable, mesh: jax.sharding.Mesh, axis: str):
    """Build a pipelined apply: (stacked_params, microbatches) -> outputs.

    stage_fn(params_slice, x) -> y must be shape-preserving in x (the
    usual transformer-block contract).
    stacked_params: pytree with leading dim = n_stages on every leaf.
    microbatches:   (n_micro, mb, ...) array (already microbatched).
    """
    n_stages = mesh.shape[axis]

    def per_stage(params, xs):
        # params: this stage's slice (leading dim 1); xs: full microbatch
        # stream, meaningful at stage 0 only (replicated over the axis).
        p = jax.tree.map(lambda a: a[0], params)
        idx = jax.lax.axis_index(axis)
        n_micro = xs.shape[0]
        T = n_micro + n_stages - 1
        ring = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            state, buf = carry
            inject = xs[jnp.clip(t, 0, n_micro - 1)]
            x_in = jnp.where(idx == 0, inject, state)
            y = stage_fn(p, x_in)
            nxt = jax.lax.ppermute(y, axis, ring)
            out_t = t - (n_stages - 1)
            valid = (idx == n_stages - 1) & (out_t >= 0)
            upd = jax.lax.dynamic_update_index_in_dim(
                buf, y, jnp.clip(out_t, 0, n_micro - 1), 0)
            buf = jnp.where(valid, upd, buf)
            return (nxt, buf), None

        init = (jnp.zeros_like(xs[0]), jnp.zeros_like(xs))
        (_, buf), _ = jax.lax.scan(tick, init, jnp.arange(T))
        # only the last stage holds real outputs; broadcast them back
        # (psum of the masked buffer over the ring)
        buf = jnp.where(idx == n_stages - 1, buf, jnp.zeros_like(buf))
        return jax.lax.psum(buf, axis)

    return shard_map(
        per_stage, mesh,
        in_specs=(P(axis), P()),       # params sharded by stage; xs replicated
        out_specs=P())


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
