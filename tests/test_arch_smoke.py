"""Per-architecture smoke tests: every assigned arch instantiates a
reduced same-family config and runs forward + one train step + one decode
step on CPU, asserting shapes and no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.models import api
from repro.optim import adamw
from repro.runtime import trainer

# interpret-mode model/kernel tests: minutes on a throttled CPU
pytestmark = pytest.mark.slow

B, S = 2, 16


def _batch(cfg, key):
    ks = jax.random.split(key, 3)
    batch = {
        "tokens": jax.random.randint(ks[0], (B, S), 0, cfg.vocab),
        "labels": jax.random.randint(ks[1], (B, S), 0, cfg.vocab),
    }
    if cfg.is_encdec:
        batch["enc_embeds"] = jax.random.normal(
            ks[2], (B, S, cfg.d_model), jnp.float32).astype(cfg.dtype)
    elif cfg.frontend == "patch":
        ft = max(cfg.frontend_tokens, 4)
        batch["frontend_embeds"] = jax.random.normal(
            ks[2], (B, ft, cfg.d_model), jnp.float32).astype(cfg.dtype)
        batch["tokens"] = batch["tokens"][:, : S - ft]
        batch["labels"] = batch["labels"][:, : S - ft]
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_and_loss(arch):
    cfg = get_smoke_config(arch)
    params = api.init_params(cfg, jax.random.key(0))
    batch = _batch(cfg, jax.random.key(1))
    logits, _, _ = api.forward(cfg, params, batch)
    vocab_padded = logits.shape[-1]
    assert vocab_padded >= cfg.vocab
    assert not bool(jnp.isnan(logits).any())
    loss, aux = api.loss_fn(cfg, params, batch)
    assert np.isfinite(float(loss))
    # random init: loss near ln(vocab)
    assert float(aux["ce"]) == pytest.approx(np.log(cfg.vocab), rel=0.35)


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_updates_and_finite(arch):
    cfg = get_smoke_config(arch)
    opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=0,
                                moment_dtype=cfg.moment_dtype)
    step = jax.jit(trainer.make_train_step(cfg, opt_cfg))
    state = trainer.init_state(cfg, opt_cfg, jax.random.key(0))
    batch = _batch(cfg, jax.random.key(1))
    new_state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    # params actually moved
    moved = jax.tree.map(
        lambda a, b: float(jnp.abs(a.astype(jnp.float32)
                                   - b.astype(jnp.float32)).max()),
        state["params"], new_state["params"])
    assert max(jax.tree.leaves(moved)) > 0
    # no NaN anywhere in the new state
    for leaf in jax.tree.leaves(new_state["params"]):
        assert not bool(jnp.isnan(leaf.astype(jnp.float32)).any())


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_then_decode(arch):
    cfg = get_smoke_config(arch)
    params = api.init_params(cfg, jax.random.key(0))
    batch = _batch(cfg, jax.random.key(1))
    batch.pop("labels")
    max_len = S + 8
    logits, cache, pos = api.prefill(cfg, params, batch, max_len=max_len)
    assert logits.shape[0] == B and not bool(jnp.isnan(logits).any())
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    logits2, cache2 = api.decode_step(cfg, params, cache, tok, pos)
    assert logits2.shape[0] == B
    assert not bool(jnp.isnan(logits2).any())
    # cache structure preserved
    assert jax.tree.structure(cache) == jax.tree.structure(cache2)


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_matches_assignment(arch):
    """The full (non-smoke) config must carry the exact assigned numbers."""
    spec = {
        "dbrx-132b": (40, 6144, 48, 8, 10752, 100352),
        "llama4-maverick-400b-a17b": (48, 5120, 40, 8, 8192, 202048),
        "seamless-m4t-large-v2": (24, 1024, 16, 16, 8192, 256206),
        "yi-6b": (32, 4096, 32, 4, 11008, 64000),
        "qwen1.5-4b": (40, 2560, 20, 20, 6912, 151936),
        "qwen2.5-3b": (36, 2048, 16, 2, 11008, 151936),
        "granite-3-2b": (40, 2048, 32, 8, 8192, 49155),
        "internvl2-2b": (24, 2048, 16, 8, 8192, 92553),
        "mamba2-2.7b": (64, 2560, None, None, 0, 50280),
        "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
        "granite-4.0-h-small": (40, 4096, 32, 8, 768, 100352),
    }[arch]
    cfg = get_config(arch)
    L, d, h, kv, ff, v = spec
    assert cfg.num_layers == L and cfg.d_model == d
    assert cfg.d_ff == ff and cfg.vocab == v
    if h is not None:
        assert cfg.num_heads == h and cfg.kv_heads == kv


def test_moe_archs_route_tokens():
    cfg = get_smoke_config("dbrx-132b")
    assert cfg.moe is not None and cfg.moe.num_experts > 0
    params = api.init_params(cfg, jax.random.key(0))
    batch = _batch(cfg, jax.random.key(1))
    loss, aux = api.loss_fn(cfg, params, batch)
    assert float(aux["lb_loss"]) > 0          # router actually engaged


def test_param_counts_plausible():
    """Analytic parameter counts should be in the ballpark of the names."""
    approx = {
        "yi-6b": 6e9, "qwen2.5-3b": 3e9, "granite-3-2b": 2.5e9,
        "mamba2-2.7b": 2.7e9, "recurrentgemma-9b": 9e9,
        "dbrx-132b": 132e9,
    }
    for arch, n in approx.items():
        got = get_config(arch).param_count()
        assert 0.5 * n < got < 1.8 * n, (arch, got)
    # llama4: ~400B total / ~17B active
    cfg = get_config("llama4-maverick-400b-a17b")
    assert 250e9 < cfg.param_count() < 550e9
    assert 10e9 < cfg.active_param_count() < 25e9
