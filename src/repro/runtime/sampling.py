"""Per-request decode policies (ISSUE 9): ``SamplingParams`` + the fused
batched sampler both serving engines emit tokens through.

The contract that makes per-request policies free at trace time:

* **Policies are operands, not constants.** A request's (temperature,
  top_k, top_p, seed) ride into the jitted step as stacked ``(B,)``
  device arrays (``policy_operands``), so one trace per prefill bucket /
  step shape serves ANY mix of greedy and sampled requests — no retrace
  per temperature value.
* **Greedy is the temperature=0 row of the sampled program.** A
  categorical draw at temperature t is ``argmax(z + G)`` with ``z`` the
  masked, temperature-scaled logits and ``G`` i.i.d. Gumbel noise; rows
  with t <= 0 multiply the noise by zero and reduce to the exact argmax
  the pre-ISSUE-9 engine computed (top-k/top-p masks always keep the
  top-1 token, so they never perturb a greedy row).
* **Stages run only when some row of the batch asks for them.** The
  top-k stage (a full-vocabulary sort and mask), the top-p stage
  (another sort, softmax, cumsum and mask) and the Gumbel draw each sit
  under a ``lax.cond`` on a batch-wide predicate of the policy operands
  (``any(top_k > 0)``, ``any(top_p < 1)``, ``any(temp > 0)``), decided
  on the device: one trace still serves every policy mix, and an
  all-greedy batch is a plain ``argmax``. Skipping is exact, not an
  approximation: a stage whose predicate is false would have been the
  identity on every row (each row's own exemption in the ``where``
  keeps all its entries, and zero times finite noise adds zero), so
  every token, greedy or sampled, is bit-identical to running all three
  stages always. The conds stay outside any ``vmap``, where they would
  become selects that run both branches. ``sampler_stages`` is the
  host's copy of the three predicates (the engine's ``sampler`` trace
  counter).
* **Per-request PRNG, position-indexed.** The key for the draw that
  decides generated token ``idx`` of request ``rid`` is
  ``fold_in(fold_in(fold_in(key(seed), rid), idx), draw)`` — a pure
  function of (seed, rid, idx), independent of batch composition, slot
  assignment, shard count or preemption history. A preempted request
  that resumes by re-prefill (or swap-in) replays the identical token
  stream; the same request served by the dense engine, the gather or
  kernel attention impl, or any TP shard count draws the same tokens.
  ``draw`` separates the independent uses of one position's key:
  ``ACCEPT_DRAW`` (speculative acceptance test) vs ``SAMPLE_DRAW``
  (the token draw itself), so the non-speculative engine and a verify
  step that rejects every draft consume the same sample stream.

Rejection-sampled speculative verification (the rule
``runtime/serving.py``'s verify step applies per drafted token): both
drafters propose deterministically (greedy argmax of the draft model /
n-gram lookup), so the proposal distribution q is a point mass and the
standard accept rule ``u < min(1, p(x)/q(x))`` reduces to ``u < p(x)``
with ``p`` the target policy's (masked, scaled) softmax. On first
rejection the engine emits a sample from the residual distribution —
``p`` with the rejected draft's mass removed and renormalized, i.e. a
gumbel-argmax over ``z`` with the draft token masked out. Marginally
each emitted token is distributed exactly as a non-speculative sample
(P(emit y != x) = (1 - p(x)) * p(y)/(1 - p(x)) = p(y)); at temperature
0, ``p`` is a point mass on the argmax, so "accept iff draft == argmax,
residual sample = argmax" — token-identical to the exact-greedy
verification it generalizes.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# Filtered-out logits get a large finite penalty rather than -inf: an
# all-masked row (possible in intermediate spellings like a residual
# whose support emptied) then still argmaxes deterministically instead
# of propagating NaN through softmax.
NEG_FILTER = -1e30

# fold_in tags separating the independent draws one generated position
# may consume (see module docstring)
ACCEPT_DRAW = 0
SAMPLE_DRAW = 1


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request decode policy, carried on ``Request.params`` from
    ``submit()`` into the traced step. Defaults are exact greedy.

    temperature: 0 = greedy (argmax); > 0 scales logits by 1/t before
        the categorical draw.
    top_k: keep only the k highest logits (0 = no top-k cut).
    top_p: nucleus filtering — keep the smallest prefix of the sorted
        distribution with cumulative mass >= top_p (1.0 = no cut).
    seed: per-request PRNG seed; None uses the engine's seed. Tokens
        are a pure function of (seed, rid, generated-token index).
    """
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: Optional[int] = None

    def validate(self) -> "SamplingParams":
        if not self.temperature >= 0.0:
            raise ValueError(
                f"temperature must be >= 0 (0 = greedy): {self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0 (0 = off): {self.top_k}")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(
                f"top_p must be in (0, 1] (1 = off): {self.top_p}")
        return self

    @property
    def is_greedy(self) -> bool:
        return self.temperature <= 0.0


GREEDY = SamplingParams()


def policy_operands(policies: Sequence[Optional[SamplingParams]],
                    rids: Sequence[int], idxs: Sequence[int],
                    default_seed: int):
    """Stack per-slot policies into the ``(B,)`` device operands the
    jitted programs consume: one dict pytree of six arrays. Dead slots
    pass ``None`` policies (greedy rows whose output the live mask
    discards). ``idxs[i]`` is slot i's next generated-token index —
    ``len(req.generated)`` — the position the step's draw decides."""
    B = len(policies)
    temp = np.zeros((B,), np.float32)
    top_k = np.zeros((B,), np.int32)
    top_p = np.ones((B,), np.float32)
    seed = np.zeros((B,), np.int32)
    for i, p in enumerate(policies):
        p = p if p is not None else GREEDY
        temp[i] = p.temperature
        top_k[i] = p.top_k
        top_p[i] = p.top_p
        s = p.seed if p.seed is not None else default_seed
        seed[i] = np.int32(s & 0x7FFFFFFF)
    return {
        "temp": jnp.asarray(temp),
        "top_k": jnp.asarray(top_k),
        "top_p": jnp.asarray(top_p),
        "seed": jnp.asarray(seed),
        "rid": jnp.asarray(np.asarray(rids, np.int32)),
        "idx": jnp.asarray(np.asarray(idxs, np.int32)),
    }


def fold_keys(seed, rid, idx) -> jax.Array:
    """(B,) int32 operands -> (B,) typed PRNG keys:
    ``fold_in(fold_in(key(seed), rid), idx)``."""
    def one(s, r, i):
        return jax.random.fold_in(jax.random.fold_in(
            jax.random.key(s), r), i)

    return jax.vmap(one)(seed, rid, idx)


def draw_keys(keys, draw: int) -> jax.Array:
    """Split a position's key into its independent draws (ACCEPT_DRAW /
    SAMPLE_DRAW)."""
    return jax.vmap(lambda k: jax.random.fold_in(k, draw))(keys)


def _top_k_mask(z, top_k):
    """Keep each row's ``top_k`` largest entries (value threshold at the
    k-th largest); rows with k <= 0 keep everything."""
    V = z.shape[-1]
    srt = jnp.sort(z, axis=-1)[..., ::-1]
    kth = jnp.take_along_axis(
        srt, jnp.clip(top_k - 1, 0, V - 1)[:, None], axis=-1)
    return jnp.where((z >= kth) | (top_k <= 0)[:, None], z, NEG_FILTER)


def _top_p_mask(z, top_p):
    """Nucleus cut: keep the smallest sorted prefix whose cumulative mass
    reaches p (the token that crosses the boundary is kept: cum - prob <
    p); rows with p >= 1 keep everything."""
    srt = jnp.sort(z, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(srt, axis=-1)
    keep_sorted = (jnp.cumsum(probs, axis=-1) - probs) < top_p[:, None]
    n_keep = jnp.maximum(keep_sorted.sum(-1), 1)
    pth = jnp.take_along_axis(srt, (n_keep - 1)[:, None], axis=-1)
    return jnp.where((z >= pth) | (top_p >= 1.0)[:, None], z, NEG_FILTER)


def scale_mask(logits, temp, top_k, top_p) -> jax.Array:
    """Temperature-scale then top-k/top-p-filter a (B, V) logit batch,
    rowwise. Returns f32 ``z`` with filtered entries at ``NEG_FILTER``;
    ``softmax(z)`` is the policy's target distribution p and
    ``argmax(z)`` its greedy token. Rows with temp <= 0 skip the scale
    (argmax is scale-invariant and both masks keep the top-1 token, so
    greedy rows are exact argmax rows regardless of k/p). Each filter
    runs only if some row sets it (module docstring)."""
    z = logits.astype(jnp.float32)
    z = z / jnp.where(temp > 0, temp, 1.0)[:, None]
    # top-k first, then top-p on the top-k survivors
    z = jax.lax.cond(jnp.any(top_k > 0), _top_k_mask,
                     lambda z, _: z, z, top_k)
    return jax.lax.cond(jnp.any(top_p < 1.0), _top_p_mask,
                        lambda z, _: z, z, top_p)


def gumbel_argmax(z, temp, keys) -> jax.Array:
    """The fused categorical-or-greedy draw: per-row Gumbel noise is
    zeroed where temp <= 0, so ``argmax(z + noise)`` is a categorical
    sample from softmax(z) on sampled rows and the exact argmax on
    greedy rows — one program, no retrace. The noise is drawn only if
    some row samples; an all-greedy batch is ``argmax(z)``."""
    def noisy(z):
        g = jax.vmap(lambda k: jax.random.gumbel(
            k, z.shape[-1:], jnp.float32))(keys)
        return z + jnp.where(temp > 0, 1.0, 0.0)[:, None] * g

    z = jax.lax.cond(jnp.any(temp > 0), noisy, lambda z: z, z)
    return jnp.argmax(z, axis=-1).astype(jnp.int32)


def sample_rows(logits, pol, offset: int = 0) -> jax.Array:
    """Sample one token per row of a (B, V) logit batch under the
    stacked policies ``pol`` (a ``policy_operands`` pytree). ``offset``
    shifts the generated-token index (a verify step's row t decides
    position idx + t). Callers slice logits to the real vocab first."""
    z = scale_mask(logits, pol["temp"], pol["top_k"], pol["top_p"])
    keys = draw_keys(
        fold_keys(pol["seed"], pol["rid"], pol["idx"] + offset),
        SAMPLE_DRAW)
    return gumbel_argmax(z, pol["temp"], keys)


def request_params(req, default: SamplingParams) -> SamplingParams:
    """Resolve a request's effective policy: its own ``params`` if set,
    else the engine default — validated either way."""
    p = getattr(req, "params", None)
    return (p if p is not None else default).validate()


def sampler_stages(policies: Sequence[Optional[SamplingParams]]
                   ) -> Dict[str, float]:
    """Which of the sampler's gated stages a batch of slot policies runs
    (1.0) or skips (0.0): the host's copy of the device predicates in
    ``scale_mask`` and ``gumbel_argmax``. Dead slots (None) are greedy
    rows, as in ``policy_operands``."""
    live = [p if p is not None else GREEDY for p in policies]
    return {"topk": float(any(p.top_k > 0 for p in live)),
            "topp": float(any(p.top_p < 1.0 for p in live)),
            "noise": float(any(p.temperature > 0 for p in live))}


def summarize(policies: List[Optional[SamplingParams]]) -> str:
    """Human-readable policy mix for logs/telemetry."""
    live = [p for p in policies if p is not None]
    n_greedy = sum(1 for p in live if p.is_greedy)
    return f"{n_greedy} greedy / {len(live) - n_greedy} sampled"
