"""granite-4.0-h-small (Mamba-2 mixers and NoPE GQA, each followed by a
dropless routed FFN over the experts held here) against the plain float32
reference of the benchmark (``bench/reference/granite_moe_hybrid.py``),
at smoke size on the CPU with seeded random weights.

The program runs in float32 here (weights, activations and KV), so it
and the reference differ only by float32 rounding and the order of
summation (chunked scan against the token recurrence, flash attention,
grouped expert calls): about 1e-6 of the largest value. The tolerance
sits twenty times above that and far under what bfloat16 products do
(about 2e-2 here), so a reference or program computing in bfloat16
fails it."""
import dataclasses
import hashlib
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT)]

from bench import check, weights  # noqa: E402
from bench.reference import granite_moe_hybrid as ref  # noqa: E402
from repro.configs import get_config, get_smoke_config  # noqa: E402
from repro.models import api, layers, ssm  # noqa: E402
from repro.runtime.serving import (PagedServingEngine, Request,  # noqa: E402
                                   expert_series)
from repro.runtime.trace import Tracer  # noqa: E402

SEED = 2 ** 33 + 5
# float32 program against the float32 reference: the prefill-and-decode
# logits differed by at most 1.0e-6 of the largest logit; the program in
# bfloat16 by 1.8e-2
REL = 2e-5


def _cfg(**over):
    """The smoke preset in float32, 4 of its 8 experts held (2..5)."""
    base = dict(dtype="float32", kv_cache_dtype="float32",
                experts_first=2, experts_held=4)
    return dataclasses.replace(get_smoke_config("granite-4.0-h-small"),
                               **{**base, **over})


def model_of(cfg) -> dict:
    """A configuration file's ``model`` object for ``cfg``."""
    kinds = (cfg.hybrid.pattern * cfg.num_layers)[:cfg.num_layers]
    s = cfg.ssm
    return {
        "tie_word_embeddings": True, "position_embedding_type": "nope",
        "attention_bias": False,
        "layer_types": ["attention" if k.startswith("attn") else "mamba"
                        for k in kinds],
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.kv_heads, "intermediate_size": cfg.d_ff,
        "shared_intermediate_size": cfg.moe.shared_d_ff,
        "vocab_size": cfg.vocab, "router_experts": cfg.moe.num_experts,
        "num_local_experts": cfg.held_experts[1],
        "first_local_expert": cfg.held_experts[0],
        "num_experts_per_tok": cfg.moe.top_k,
        "mamba_n_heads": s.expand * cfg.d_model // s.head_dim,
        "mamba_d_head": s.head_dim, "mamba_d_state": s.state_dim,
        "mamba_n_groups": s.num_groups, "mamba_d_conv": s.conv_width,
        "rms_norm_eps": cfg.norm_eps,
        "embedding_multiplier": cfg.embedding_multiplier,
        "residual_multiplier": cfg.residual_multiplier,
        "attention_multiplier": cfg.attention_multiplier,
        "logits_scaling": cfg.logits_scaling}


def _setup(cfg):
    params = weights.make(cfg, SEED, ref.WEIGHT_RULES)
    return params, ref.Spec.from_model(model_of(cfg)), ref.plain(params)


def _close(got, want, rel=REL):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = np.abs(got - want).max()
    assert err <= rel * np.abs(want).max(), (err, np.abs(want).max())


def test_full_preset_counts_32b_total_and_8_8b_active():
    cfg = get_config("granite-4.0-h-small")
    assert cfg.param_count() == pytest.approx(32.2e9, rel=0.02)
    assert cfg.active_param_count() == pytest.approx(8.8e9, rel=0.02)


def _routed_layer(cfg, params, j=0):
    return jax.tree.map(lambda a: a[0], params["blocks"]["scan"][str(j)])


def test_routed_layer_with_every_expert_held_is_a_dense_token_loop():
    cfg = _cfg(experts_first=0, experts_held=0)
    params = weights.make(cfg, SEED, ref.WEIGHT_RULES)
    p = _routed_layer(cfg, params)["moe"]
    x = jax.random.normal(jax.random.key(3), (2, 11, cfg.d_model))
    out, rows = layers.routed_apply(cfg, p, x)
    w = {k: np.asarray(v, np.float64) for k, v in p.items() if k != "shared"}
    sh = {k: np.asarray(v, np.float64) for k, v in p["shared"].items()}

    def swiglu(v, wg, wi, wo):
        g = v @ wg
        return (g / (1 + np.exp(-g)) * (v @ wi)) @ wo

    want = np.zeros(x.shape)
    xs = np.asarray(x, np.float64)
    for b in range(2):
        for t in range(11):
            v = xs[b, t]
            logit = v @ w["router"]
            top = np.argsort(-logit)[:cfg.moe.top_k]
            g = np.exp(logit[top] - logit[top].max())
            g /= g.sum()
            want[b, t] = swiglu(v, sh["wg"], sh["wi"], sh["wo"]) + sum(
                gk * swiglu(v, w["wg"][e], w["wi"][e], w["wo"][e])
                for gk, e in zip(g, top))
    _close(out, want)
    # dropless: every token's top-k rows, all of them held here
    assert int(rows.sum()) == 2 * 11 * cfg.moe.top_k


def test_expert_shares_sum_to_the_uncut_reference_layer():
    """Four chips of a 4-way expert-parallel deployment, each holding 2
    of the 8 experts: their routed parts, and the shared expert (which
    every chip computes alike) once, add up to the reference's uncut
    layer."""
    full = _cfg(experts_first=0, experts_held=0)
    params, spec, w = _setup(full)
    p = _routed_layer(full, params)["moe"]
    x = jax.random.normal(jax.random.key(4), (1, 13, full.d_model))
    shared = layers.mlp_apply(full, p["shared"], x)
    total = shared
    for first in range(0, 8, 2):
        cut = dataclasses.replace(full, experts_first=first, experts_held=2)
        share = {**p, **{k: p[k][first:first + 2] for k in ("wg", "wi",
                                                            "wo")}}
        out, _ = layers.routed_apply(cut, share, x)
        total = total + (out - shared)
    with jax.default_matmul_precision("highest"):
        want = ref._routed(spec, "f32", x[0],
                           jax.tree.map(lambda a: a[0], w["period"][0]))
    _close(total[0], want)


def test_routed_output_does_not_depend_on_the_batch():
    cfg = _cfg(dtype="bfloat16")
    params = weights.make(cfg, SEED, ref.WEIGHT_RULES)
    p = _routed_layer(cfg, params)["moe"]
    x = jax.random.normal(jax.random.key(5), (4, 9, cfg.d_model)
                          ).astype(jnp.bfloat16)
    together, _ = layers.routed_apply(cfg, p, x)
    alone, _ = layers.routed_apply(cfg, p, x[2:3])
    np.testing.assert_array_equal(np.asarray(together[2:3], np.float32),
                                  np.asarray(alone, np.float32))


def test_mamba_mixer_matches_the_token_recurrence_gate_before_norm():
    """The chunked SSD prefill and the one-token decode step against the
    reference's token-by-token recurrence, which gates before the norm:
    norm(y * silu(z)). (The program gated after it, norm(y) * silu(z),
    before this test: off by the size of the output.)"""
    cfg = _cfg()
    params, spec, w = _setup(cfg)
    b = _routed_layer(cfg, params)
    lw = jax.tree.map(lambda a: a[0], w["period"][0])
    S = 21
    x = jax.random.normal(jax.random.key(6), (1, S, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        want = ref._mamba(spec, "f32", x[0], lw)
    out, state = ssm.ssm_apply(cfg, b["mixer"], x[:, :S - 3],
                               return_state=True)
    _close(out[0], want[:S - 3])
    cache = {"ssm": state["ssm"], "conv": state["conv"]}
    for t in range(S - 3, S):
        y, cache = ssm.ssm_decode(cfg, b["mixer"], x[:, t:t + 1], cache)
        _close(y[0, 0], want[t])


def test_mixer_state_reuses_the_input_projection():
    """``ssm_apply(return_state=True)`` takes the conv state from the one
    in_proj product: two matrix products (in and out), not three."""
    cfg = get_smoke_config("mamba2-2.7b")
    p = jax.tree.map(lambda a: a[0], api.init_params(
        cfg, jax.random.key(0))["blocks"]["scan"]["0"]["mixer"])
    x = jnp.ones((1, 8, cfg.d_model), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda p, x: ssm.ssm_apply(
        cfg, p, x, return_state=True))(p, x)
    dots = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "dot_general"]
    assert len(dots) == 2


def test_prefill_then_decode_logits_match_the_reference():
    cfg = _cfg()
    params, spec, w = _setup(cfg)
    S, n = 19, 5
    toks = jax.random.randint(jax.random.key(7), (S + n,), 0, cfg.vocab)
    want = np.asarray(ref.logits_at(spec, w, toks, jnp.arange(S - 1, S + n)))
    logits, cache, pos = api.prefill(cfg, params, {"tokens": toks[None, :S]},
                                     max_len=S + n)
    got = [logits[0, :cfg.vocab]]
    for t in range(S, S + n):
        lg, cache = api.decode_step(cfg, params, cache, toks[None, t:t + 1],
                                    pos)
        pos = pos + 1
        got.append(lg[0, :cfg.vocab])
    _close(jnp.stack(got), want[:n + 1])


@pytest.mark.slow
def test_paged_engine_serves_what_the_reference_puts_first():
    """Prompts that cross page boundaries and buckets, served greedily by
    the paged engine (state slots, pages, the flash-decode kernel in
    interpret mode, the grouped expert kernels): at every served position
    the token lies within float32 rounding of the reference's best
    logit. The engine's tracer records the ``experts`` counter each step
    and a span around each slot's state write and reset."""
    cfg = _cfg()
    params, spec, w = _setup(cfg)
    tr = Tracer(enabled=True)
    eng = PagedServingEngine(cfg, params, slots=3, max_len=64, page_size=8,
                             num_pages=24, tracer=tr)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, n).tolist(),
                    max_new=m)
            for i, (n, m) in enumerate([(13, 9), (30, 6), (7, 12), (21, 5)])]
    eng.run_to_completion(reqs)
    served = [(r.prompt, r.generated) for r in reqs]
    assert all(len(g) == r.max_new for (_, g), r in zip(served, reqs))
    nums = check.compare(ref, spec, w, served, max_len=64, max_out=12)
    top = max(np.abs(check.reference_logits(
        ref, spec, w, p, g, s_pad=64, n_pad=12)).max() for p, g in served)
    assert nums["max_gap"] <= REL * top
    names = [e["name"] for e in tr.events()]
    assert names.count("state_write") == 4 and \
        names.count("state_reset") == 4
    rows = [e["args"] for e in tr.events()
            if e["name"] == "experts"]
    assert len(rows) == eng.decode_steps and all(
        0 <= r["hit"] <= 4 and r["rows_max"] >= r["rows_mean"]
        for r in rows)


def test_expert_series_reads_rows_per_held_expert():
    rows = np.array([[0, 3, 1, 0], [2, 2, 0, 0]])
    assert expert_series(rows) == {"rows_mean": 1.0, "rows_max": 3.0,
                                   "hit": 2.0}


def _digest(a) -> str:
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:16]


# sha256 (16 hex digits) of the smoke forward's logits and of one decode
# step's logits after a prefill, as the program computed them before the
# Granite fields existed
BEFORE = {"qwen2.5-3b": ("b902b25cfed596fa", "2a884937e44d227c"),
          "granite-3-2b": ("3fcebe0fa82a6389", "c5d727b40bce553d")}


@pytest.mark.parametrize("arch", sorted(BEFORE))
def test_new_fields_at_their_defaults_leave_dense_presets_bitwise(arch):
    cfg = get_smoke_config(arch)
    params = api.init_params(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab)
    logits, _, _ = api.forward(cfg, params, {"tokens": toks})
    lg, cache, pos = api.prefill(cfg, params, {"tokens": toks}, max_len=24)
    l2, _ = api.decode_step(cfg, params, cache, jnp.argmax(
        lg, -1)[:, None].astype(jnp.int32), pos)
    assert (_digest(logits), _digest(l2)) == BEFORE[arch]
