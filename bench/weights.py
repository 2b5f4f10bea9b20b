"""Random weights from the seed, made by the benchmark in one jitted call
on the device, in the dtype they are served in.

The program's parameter layout (which leaves, what shapes) is read from
``api.param_shapes`` without running anything; the values are the
benchmark's own, so the plain reference and the program are handed the
same arrays and neither depends on how the other makes them. Biases and
norm scales are drawn away from zero and one, so the check sees them.

The rules here cover the leaves of a dense GQA tree: norm scales, q/k/v
biases, the embedding and the matrices in ``MATRICES``. A leaf of any
other name is drawn by the rule the configuration's reference module
supplies for it (``WEIGHT_RULES``, ``bench/reference/__init__.py``);
one that no rule covers stops ``make`` with its path.
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
# matrix leaves drawn at fan_in ** -0.5
MATRICES = ("wq", "wk", "wv", "wo", "wi", "wg", "head")


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


def _leaf(key, names, sd, rules):
    leaf = names[-1]
    if leaf in rules:
        return rules[leaf](key, tuple(names), sd.shape).astype(sd.dtype)
    if leaf not in ("scale", "bq", "bk", "bv", "embed") + MATRICES:
        raise ValueError(f"no weight rule for leaf {'/'.join(names)} "
                         f"{sd.shape}: the configuration's reference module "
                         f"supplies none (WEIGHT_RULES)")
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


@functools.partial(jax.jit, static_argnums=(0, 1))
def _make(cfg, rules, key):
    shapes = api.param_shapes(cfg)
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    by_name = dict(rules)
    leaves = [_leaf(jax.random.fold_in(key, i), _names(p), sd, by_name)
              for i, (p, sd) in enumerate(flat)]
    return jax.tree_util.tree_unflatten(tree, leaves)


def make(cfg, seed: int, rules=None):
    """The program's parameter tree for ``cfg``, drawn from ``seed``.
    ``rules`` maps a leaf name to ``rule(key, path, shape)``, which
    returns that leaf's float32 values; it is consulted before the rules
    here."""
    rules = tuple(sorted((rules or {}).items()))
    # repro-lint: disable=host-sync — set-up waits for the weights once
    return jax.block_until_ready(_make(cfg, rules, seed_key(seed)))
