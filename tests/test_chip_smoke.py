"""chip_smoke.py's phase functions at smoke size on the CPU.

The script's ``main()`` refuses anything but a TPU; its phases are plain
functions, imported here so the serve path and the teacher-forced float32
reference check it relies on run on every change (Pallas in interpret
mode). The four-way phase needs four devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``).
"""
import dataclasses
import importlib.util
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import api
from repro.runtime.serving import Request

ROOT = Path(__file__).resolve().parents[1]
# the largest reference-logit gap a sound full-width run has read on a TPU
# v5e (granite-3-2b, TP=4; PERF.md): a token that trails the argmax by
# twice this is a fault the check must reject
SOUND_GAP = 0.0468


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def served(smoke):
    cfg = get_smoke_config("qwen2.5-3b")
    params = api.init_params(cfg, jax.random.key(0))
    eng = smoke.build_engine(cfg, params, slots=3, max_len=64)
    prompts = smoke.make_prompts(cfg, (5, 17, 30, 9), seed=0)
    reqs = smoke.serve_phase(eng, prompts, max_new=6)
    return cfg, params, eng, reqs


def test_device_phase_refuses_cpu(smoke):
    with pytest.raises(SystemExit, match="needs a TPU"):
        smoke.device_phase(1)


def test_main_refuses_cpu_before_any_phase(smoke, capsys, monkeypatch):
    # the tests never turn on the persistent compile cache
    monkeypatch.setattr(smoke, "enable_compile_cache", lambda: None)
    with pytest.raises(SystemExit):
        smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "granite-3-2b"])
def test_kernel_phase(smoke, arch):
    err = smoke.kernel_phase(get_smoke_config(arch), batch=3, n_blocks=4,
                             seed=1)
    assert err <= smoke.KERNEL_TOL


def test_serve_phase_builds_the_launcher_engine(smoke, served):
    _, _, eng, reqs = served
    assert type(eng).__name__ == "PagedServingEngine"
    assert eng.attn_impl == "kernel"
    assert [len(r.generated) for r in reqs] == [6] * 4
    text = eng.lower_step().as_text()
    # interpret mode on the CPU: the kernel is inlined, not a custom call
    assert "func.func" in text and "tpu_custom_call" not in text


def test_reference_phase_passes_engine_tokens(smoke, served):
    cfg, params, _, reqs = served
    out = smoke.reference_phase(cfg, params, reqs)
    assert out["positions"] == 24
    assert out["max_gap"] <= smoke.LOGIT_TOL
    assert out["near_ties"] <= smoke.MAX_NEAR_TIE_SHARE * 24


def test_reference_phase_rejects_a_wrong_token(smoke, served):
    cfg, params, _, reqs = served
    bad = [dataclasses.replace(r, generated=list(r.generated))
           for r in reqs]
    # swap one token for the one the engine ranks last at that position:
    # any honest reference must see it far below the argmax
    ref = smoke._reference_logits(
        cfg, params,
        jax.numpy.asarray([bad[1].prompt + bad[1].generated[:3]]),
        jax.numpy.asarray([[len(bad[1].prompt) + 2]]))
    bad[1].generated[3] = int(ref[0, 0].argmin())
    with pytest.raises(AssertionError, match="request 1 token 3"):
        smoke.reference_phase(cfg, params, bad)


def _ranked(smoke, cfg, params, r):
    """The float32 reference's runner-up token and top-1/top-2 margin at
    each of ``r``'s generated positions."""
    ctx = jax.numpy.asarray([r.prompt + r.generated[:-1]])
    pos = jax.numpy.asarray([len(r.prompt) - 1 + np.arange(len(r.generated))])
    logits = np.asarray(smoke._reference_logits(cfg, params, ctx, pos))[0]
    two = np.argsort(logits, -1)[:, -2:]
    top = np.take_along_axis(logits, two, -1)
    return two[:, 0], top[:, 1] - top[:, 0]


def _plant_runner_up(reqs, ranked, picks):
    """Copies of ``reqs``: where ``picks[i]`` names a position, request
    i's token there becomes the runner-up and its output ends after it,
    so no later token sees the changed context."""
    out = []
    for r, (second, _), t in zip(reqs, ranked, picks):
        r = dataclasses.replace(r, generated=list(r.generated))
        if t is not None:
            r.generated[t] = int(second[t])
            del r.generated[t + 1:]
        out.append(r)
    return out


def _serve_with_bad_page(smoke, cfg, params, prompts, corrupt):
    """Serve ``prompts`` with request 2's first KV page replaced, after
    the first decode tick, by ``corrupt(pool, page, other)`` in every
    layer (``other`` is request 1's first page)."""
    eng = smoke.build_engine(cfg, params, slots=4, max_len=64)
    reqs = [Request(rid=i, prompt=list(p), max_new=6)
            for i, p in enumerate(prompts)]
    for r in reqs:
        assert eng.submit(r)
    eng.step()
    table = np.asarray(eng.block_table)
    page, other = int(table[2, 0]), int(table[1, 0])
    eng.cache = jax.tree.map(
        lambda a: corrupt(a, page, other) if a.ndim == 5 else a, eng.cache)
    while any(len(r.generated) < r.max_new for r in reqs):
        eng.step()
    return reqs


@pytest.mark.parametrize("fault", ["runner_up", "near_ties", "stale_page",
                                   "scaled_page"])
def test_reference_phase_rejects_a_subtle_fault(smoke, served, fault):
    # faults a sound run never shows but whose tokens stay plausible: the
    # runner-up token where it trails by just over twice SOUND_GAP,
    # runner-ups within LOGIT_TOL at more than MAX_NEAR_TIE_SHARE of the
    # tokens, a stale KV page (another request's) and a KV page scaled by
    # 1.5
    cfg, params, _, reqs = served
    if fault in ("runner_up", "near_ties"):
        ranked = [_ranked(smoke, cfg, params, r) for r in reqs]
    if fault == "runner_up":
        last = [m[-1] if m[-1] > 2 * SOUND_GAP else np.inf
                for _, m in ranked]
        i = int(np.argmin(last))
        assert np.isfinite(last[i])
        bad = _plant_runner_up(reqs, ranked, [
            len(r.generated) - 1 if j == i else None
            for j, r in enumerate(reqs)])
        match = f"request {reqs[i].rid} token"
    elif fault == "near_ties":
        picks = [next((t for t, x in enumerate(m) if x <= smoke.LOGIT_TOL),
                      None)
                 for _, m in ranked]
        bad = _plant_runner_up(reqs, ranked, picks)
        planted = sum(t is not None for t in picks)
        assert planted > smoke.MAX_NEAR_TIE_SHARE * sum(
            len(r.generated) for r in bad)
        match = "not the float32 argmax"
    else:
        corrupt = ((lambda a, p, o: a.at[:, p].set(a[:, o]))
                   if fault == "stale_page"
                   else (lambda a, p, o: a.at[:, p].multiply(1.5)))
        bad = _serve_with_bad_page(smoke, cfg, params,
                                   [r.prompt for r in reqs], corrupt)
        match = "request 2"
    with pytest.raises(AssertionError, match=match):
        smoke.reference_phase(cfg, params, bad)


def test_serve_one_chip_smoke(smoke):
    cfg = get_smoke_config("granite-3-2b")
    params = api.init_params(cfg, jax.random.key(2))
    out = smoke.serve_one_chip(
        cfg, params, smoke.make_prompts(cfg, (12, 40), seed=2), max_len=64,
        max_new=4, slots=2)
    assert out["positions"] == 8


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")
def test_serve_four_ways_smoke(smoke):
    # kv_heads and d_ff divisible by 4, as granite-3-2b's are at full width
    cfg = dataclasses.replace(get_smoke_config("granite-3-2b"),
                              num_heads=8, kv_heads=4)
    params = api.init_params(cfg, jax.random.key(3))
    smoke.serve_four_ways(cfg, params,
                          smoke.make_prompts(cfg, (6, 20, 33, 11), seed=3),
                          max_len=64, max_new=5, slots=2)
