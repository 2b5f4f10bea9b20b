"""Random weights from the seed, made by the benchmark in one jitted call
on the device, in the dtype they are served in.

The program's parameter layout (which leaves, what shapes) is read from
``api.param_shapes`` without running anything; the values are the
benchmark's own, so the plain reference and the program are handed the
same arrays and neither depends on how the other makes them. Biases and
norm scales are drawn away from zero and one, so the check sees them.

``plain`` gives the same arrays under the reference's names.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import api

# standard deviations: embedding (the tied head reads it too), biases,
# and the spread of norm scales around 1; matrices use fan_in ** -0.5
EMBED_STD = 0.02
BIAS_STD = 0.5
SCALE_STD = 0.1


def seed_key(seed: int) -> jax.Array:
    """A key for any whole number up to 64 bits."""
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def _fan_in(names, shape) -> int:
    """Inputs summed into each output of a matrix leaf (layer axis
    first where stacked)."""
    leaf = names[-1]
    if leaf in ("wq", "wk", "wv"):
        return shape[-3]
    if leaf == "wo" and "attn" in names:
        return shape[-3] * shape[-2]
    return shape[-2]


def _leaf(key, names, sd):
    leaf = names[-1]
    z = jax.random.normal(key, sd.shape, jnp.float32)
    if leaf == "scale":
        x = 1.0 + SCALE_STD * z
    elif leaf in ("bq", "bk", "bv"):
        x = BIAS_STD * z
    elif leaf == "embed":
        x = EMBED_STD * z
    else:
        x = z * _fan_in(names, sd.shape) ** -0.5
    return x.astype(sd.dtype)


def _names(path):
    return [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]


@functools.partial(jax.jit, static_argnums=0)
def _make(cfg, key):
    shapes = api.param_shapes(cfg)
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    leaves = [_leaf(jax.random.fold_in(key, i), _names(p), sd)
              for i, (p, sd) in enumerate(flat)]
    return jax.tree_util.tree_unflatten(tree, leaves)


def make(cfg, seed: int):
    """The program's parameter tree for ``cfg``, drawn from ``seed``."""
    # repro-lint: disable=host-sync — set-up waits for the weights once
    return jax.block_until_ready(_make(cfg, seed_key(seed)))


def plain(tree) -> dict:
    """The same arrays under the reference's flat names (layer-stacked,
    layer axis first). Raises if the program's layout is not the dense
    GQA stack the reference computes."""
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
