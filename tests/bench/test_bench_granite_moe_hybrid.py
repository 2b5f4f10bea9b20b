"""The granite-4.0-h-small configuration and its reference module
(``bench/reference/granite_moe_hybrid.py``): the file runs the cut the
program states, the module's weight rules and counts, the two expert
kernel readers, and a whole smoke run through the harness."""
import argparse
import dataclasses
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(ROOT / "tests")]

from bench import run as bench_run  # noqa: E402
from bench import weights  # noqa: E402
from bench.reference import granite_moe_hybrid as ref  # noqa: E402
from repro.configs import get_smoke_config  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.models import api  # noqa: E402

CONF_PATH = ROOT / "bench" / "configs" / "hybrid" / "granite-4.0-h-small.json"
CONF = json.loads(CONF_PATH.read_text())
PEAKS = {"bf16_flops_per_s": 1e4, "hbm_bytes_per_s": 100.0}
# one Mamba layer and one attention layer, 2 of 4 experts held
TOY = ref.Spec(layer_types=("mamba", "attention"), d=8, heads=2, kv_heads=1,
               head_dim=4, d_ff=4, shared_ff=6, vocab=10, experts=4, held=2,
               first=0, top_k=2, m_heads=2, m_head_dim=4, d_state=3,
               groups=1, conv=4, eps=1e-5, emb_mult=1.0, res_mult=1.0,
               attn_mult=0.5, logits_scaling=1.0)


def test_the_configuration_names_this_module_and_its_cell_exists():
    mod = bench_run.load_reference(CONF)
    assert Path(mod.__file__) == ROOT / "bench" / "reference" / \
        "granite_moe_hybrid.py"
    assert all(hasattr(mod, a) for a in bench_run.REFERENCE_API)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = next(c for c in spec["configs"] if c["name"] == CONF["name"])
    assert ROOT / conf["file"] == CONF_PATH
    assert conf["reduced"] == CONF["reduced"]
    cell = next(w for w in spec["workloads"]
                if w["name"] == "granite-4.0-h-small.docqa")
    assert cell["chips"] == 1 and bench_run.traffic.load_mix(
        cell["traffic"])["clients"] == CONF["deployment"]["flags"]["slots"]


def test_the_program_runs_the_cut_the_file_states():
    cfg = bench_run.program_config(CONF)
    m = CONF["model"]
    assert cfg.num_layers == m["num_hidden_layers"] == 10
    assert cfg.held_experts == (m["first_local_expert"],
                                m["num_local_experts"]) == (0, 9)
    assert (cfg.d_model, cfg.d_ff, cfg.moe.shared_d_ff, cfg.vocab) == (
        m["hidden_size"], m["intermediate_size"],
        m["shared_intermediate_size"], m["vocab_size"])
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling, cfg.norm_eps) == (
        m["embedding_multiplier"], m["residual_multiplier"],
        m["attention_multiplier"], m["logits_scaling"], m["rms_norm_eps"])
    kinds = ["attention" if k.startswith("attn") else "mamba"
             for k in cfg.hybrid.pattern]
    assert kinds == m["layer_types"]
    shapes = api.param_shapes(cfg)
    moe = shapes["blocks"]["scan"]["0"]["moe"]
    assert moe["router"].shape == (1, 4096, m["router_experts"])
    assert moe["wg"].shape == (1, 9, 4096, 768)
    # about 2.42B parameters: 4.83 GB in bfloat16
    n = sum(a.size for a in jax.tree.leaves(shapes))
    assert n == pytest.approx(2.42e9, rel=0.01)
    ap = argparse.ArgumentParser()
    serve.add_server_flags(ap)
    args = ap.parse_args(bench_run.server_flags(
        CONF["deployment"]["flags"]))
    assert (args.slots, args.max_len) == (24, 16384)


def test_the_top_level_keys_are_the_model_as_run_and_only_reduced_ones_differ():
    # the file's top level holds the published config.json keys as run;
    # ``model`` (what the harness and the reference read) says the same
    m = CONF["model"]
    keys = [k for k in m if k not in ("router_experts", "first_local_expert")]
    assert all(k in CONF and CONF[k] == m[k] for k in keys)
    assert sorted(CONF["published"]) == sorted(CONF["reduced"])
    assert all(CONF["published"][k] != CONF[k] for k in CONF["reduced"])
    assert CONF["published"]["layer_types"][:10] == CONF["layer_types"]


def test_weight_rules_draw_mamba_2_initial_ranges():
    cfg = get_smoke_config("granite-4.0-h-small")
    tree = weights.make(cfg, 2 ** 33 + 7, ref.WEIGHT_RULES)
    mix = tree["blocks"]["scan"]["0"]["mixer"]
    a = -np.exp(np.asarray(mix["A_log"]))
    assert (a <= -1).all() and (a >= -16).all()
    dt = np.asarray(jax.nn.softplus(mix["dt_bias"]))
    assert (dt >= 1e-3 * 0.999).all() and (dt <= 0.1 * 1.001).all()
    for leaf in ("conv_w", "conv_b"):
        v = np.asarray(mix[leaf], np.float32)
        assert np.abs(v).max() <= 0.5 and v.std() > 0.1
    d = np.asarray(mix["D"])
    assert (d >= 0.5).all() and (d <= 1.5).all()
    router = np.asarray(tree["blocks"]["scan"]["0"]["moe"]["router"])
    assert router.std() == pytest.approx(cfg.d_model ** -0.5, rel=0.2)


def test_hand_counts():
    # in_proj 8 x (2*8 + 2*3 + 2) + out_proj 8 x 8
    assert TOY.mamba_params == 192 + 64
    # q,k,v 8 x (2 + 2) x 4; o 2 x 4 x 8
    assert TOY.attn_params == 128 + 64
    assert TOY.expert_params == 96 and TOY.ffn_params == 32 + 144
    assert TOY.experts_here == 1.0
    # f32 state 2 x 4 x 3 and bf16 conv window 3 x (8 + 6), one layer
    assert TOY.state_bytes == 96 + 84
    assert TOY.kv_bytes_per_token == 16
    mix = 2 * 4 * 14 + 4 * 24            # conv, recurrence
    body = 2 * (256 + 192 + 2 * (176 + 96))
    assert TOY.token_flops(5, False) == body + mix + 4 * 2 * 4 * 5
    assert TOY.token_flops(5, True) == body + mix + 4 * 2 * 4 * 5 + 160
    assert TOY.prefill_flops(3) == 3 * (body + mix) + 4 * 2 * 4 * 6 + 160
    assert TOY.paged_attn_bytes([5, 3]) == 8 * 16 + 2 * 2 * 2 * 4 * 2
    assert TOY.paged_attn_flops([5, 3]) == 4 * 2 * 4 * 8
    # 1 token hits each held expert with chance 2/4: one of the two held
    assert TOY.expert_bytes(1) == 2 * (1.0 * 96 + 2 * 1 * 8) * 2
    assert TOY.expert_flops(3) == 2 * 2 * 3 * 96
    # 2 tokens: 2 x (1 - 1/4) held experts hit
    weights_b = (256 + 192 + 2 * 176 + 80) * 2 + 2 * 1.5 * 96 * 2
    assert TOY.decode_bytes([5, 3]) == weights_b + 2 * 2 * 180 + 192


def _run(op_time, steps, prefills, counts=TOY):
    trace = SimpleNamespace(busy_s=2.0, chips=1, op_time=lambda m: (
        sum(t for n, t in op_time if m(n)) or None))
    log = SimpleNamespace(steps=steps, prefills=prefills)
    return SimpleNamespace(trace=trace, traced=(0.0, 10.0), log=log,
                           counts=counts, peaks=PEAKS)


OPS = [("_experts_up.3", 0.75), ("_experts_down.4", 0.25),
       ("_paged.9", 5.0), ("fusion.1", 1.0)]


def test_expert_readers_on_a_made_up_trace():
    run = _run(OPS, steps=[(1.0, 2.0, [5, 3]), (3.0, 11.0, [7])],
               prefills=[(4.0, 5.0, 3)])
    need = sum(max(TOY.expert_bytes(n) / 100.0, TOY.expert_flops(n) / 1e4)
               for n in (2, 3))
    assert bench_run.read_metric("experts_roofline", run) == \
        pytest.approx(100 * need / 1.0)
    assert bench_run.read_metric("experts_device_share", run) == \
        pytest.approx(50.0)


def test_expert_readers_find_nothing_without_the_kernel_or_its_counts():
    steps = [(1.0, 2.0, [5])]
    no_kernel = _run(OPS[2:], steps, [])
    assert bench_run.read_metric("experts_roofline", no_kernel) is None
    assert bench_run.read_metric("experts_device_share", no_kernel) is None
    dense = _run(OPS, steps, [], counts=SimpleNamespace())
    assert bench_run.read_metric("experts_roofline", dense) is None
    untraced = SimpleNamespace(trace=None, counts=TOY)
    assert bench_run.read_metric("experts_roofline", untraced) is None
    assert bench_run.read_metric("experts_device_share", untraced) is None


@pytest.mark.slow
def test_a_smoke_run_through_the_harness_is_correct():
    """A closed-loop smoke cell of the model through ``run_cell``, bf16 as
    served, 4 of 8 experts held, checked against this module with its
    controls: correct, and the fp8 reference's tokens lie further below
    the best than the program's."""
    from test_granite_hybrid import model_of
    cfg = dataclasses.replace(get_smoke_config("granite-4.0-h-small"),
                              experts_first=2, experts_held=4)
    conf = {"model": model_of(cfg), "reference": CONF["reference"],
            "deployment": {"flags": {"slots": 4, "max_len": 128,
                                     "page_size": 16, "num_pages": 32}},
            # at this size the program's widest gap read at most 1.05e-4
            # over three seeds and the fp8 control's at least 6.4e-4:
            # the limit sits between
            "check": {"max_gap": 3e-4}}
    mix = {"loop": "closed", "clients": 4, "first_wave": "staggered",
           "prompt_tokens": {"dist": "uniform", "min": 40, "max": 100},
           "output_tokens": {"dist": "uniform", "min": 4, "max": 12}}
    out, _ = bench_run.run_cell({"name": "smoke", "chips": 1}, conf, mix,
                                cfg=cfg, seed=2 ** 33 + 3, seconds=1.0,
                                trace=False, control=True)
    assert out["correct"] is True and out["failed"] == 0
    assert out["numbers"]["fp8_max_gap"] > conf["check"]["max_gap"]
    assert jnp.isfinite(out["numbers"]["max_gap"])
