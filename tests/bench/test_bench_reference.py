"""The configuration's ``reference`` key selects the module that checks
the served tokens, names the weights and counts the work
(``bench/run.py`` ``load_reference``): both configurations name
``dense_gqa``, a configuration without a usable module stops before any
weights are made, and a second module named only by the key does the
check and the counts of a whole smoke run. ``bench/weights.py`` draws
the dense trees as before and refuses a leaf that no rule covers."""
import hashlib
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)]

import bench_smoke  # noqa: E402
from bench import run as bench_run  # noqa: E402
from bench import weights  # noqa: E402
from bench.reference import dense_gqa  # noqa: E402
from repro.configs import get_smoke_config  # noqa: E402

CONFIGS = sorted((ROOT / "bench" / "configs").glob("*.json"))
# sha256 (first 16 hex digits) of every leaf of weights.make(smoke
# config, 2**33 + 3), as drawn before a reference could supply rules
WEIGHT_SUMS = {
    "qwen2.5-3b": {
        "blocks/scan/0/attn/bk": "bb6a8ca4c80c76f5",
        "blocks/scan/0/attn/bq": "c2f54d68138224bc",
        "blocks/scan/0/attn/bv": "7bcb20d07be87586",
        "blocks/scan/0/attn/wk": "bc89ee1be343151b",
        "blocks/scan/0/attn/wo": "0dd25ffead8bea44",
        "blocks/scan/0/attn/wq": "793cc2afca264d89",
        "blocks/scan/0/attn/wv": "5d051c338e5edef6",
        "blocks/scan/0/ln1/scale": "2c583b2c05e855e8",
        "blocks/scan/0/ln2/scale": "e2f19767d6188959",
        "blocks/scan/0/mlp/wg": "d0a1229e2a3a1111",
        "blocks/scan/0/mlp/wi": "bacd93924e98a0d5",
        "blocks/scan/0/mlp/wo": "9b544b9ad8225d12",
        "embed": "7ce74ab3e8528d0c",
        "final_norm/scale": "d66811d84240c55d",
        "head": "c162935c380e21d1",
    },
    "granite-3-2b": {
        "blocks/scan/0/attn/wk": "3d52bf51ccbd4337",
        "blocks/scan/0/attn/wo": "0da89908a2df45b6",
        "blocks/scan/0/attn/wq": "a9a8b427217a0dba",
        "blocks/scan/0/attn/wv": "bc89ee1be343151b",
        "blocks/scan/0/ln1/scale": "c3427b8a054f81f9",
        "blocks/scan/0/ln2/scale": "90fe63803990ed79",
        "blocks/scan/0/mlp/wg": "bab5e761e059b97a",
        "blocks/scan/0/mlp/wi": "0f1ddaf7291d8e70",
        "blocks/scan/0/mlp/wo": "7db451ee19bdc1ae",
        "embed": "9b2be924775e39c7",
        "final_norm/scale": "39cfe48a03e87789",
    },
}


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_each_configuration_loads_dense_gqa_through_its_key(path):
    conf = json.loads(path.read_text())
    mod = bench_run.load_reference(conf)
    assert Path(mod.__file__) == ROOT / "bench" / "reference" / "dense_gqa.py"
    assert all(hasattr(mod, a) for a in bench_run.REFERENCE_API)
    # loaded once per process: the reference's jitted forward is reused
    assert bench_run.load_reference(conf) is mod


def _lacking(tmp_path, name):
    """A module with every entry point of dense_gqa but ``name``."""
    have = [a for a in bench_run.REFERENCE_API if a != name]
    path = tmp_path / f"no_{name}.py"
    path.write_text(f"from bench.reference.dense_gqa import "
                    f"{', '.join(have)}  # noqa: F401\n")
    return str(path)


@pytest.mark.parametrize("case", ["no key", "no file", "no logits_at",
                                  "no counts"])
def test_an_unusable_reference_stops_the_run_before_weights(
        case, tmp_path, monkeypatch):
    conf = dict(bench_smoke.CONF)
    if case == "no key":
        del conf["reference"]
    elif case == "no file":
        conf["reference"] = "bench/reference/no_such_architecture.py"
    else:
        conf["reference"] = _lacking(tmp_path, case.split()[1])
    made = []
    monkeypatch.setattr(bench_run.weights, "make",
                        lambda *a, **k: made.append(a))
    with pytest.raises(SystemExit, match="reference"):
        bench_run.run_cell({"name": "smoke", "chips": 1}, conf,
                           bench_smoke.MIX, cfg=bench_smoke.CFG, seed=1,
                           seconds=1.0, trace=False)
    assert made == []


def test_the_named_module_checks_and_counts_a_whole_run():
    """bench_smoke's configuration names a module under tests/bench/data
    that wraps dense_gqa and records its calls: the check's forward, the
    weights' names and the readers' counts all go through it."""
    rec = bench_run.load_reference(bench_smoke.CONF)
    assert Path(rec.__file__).parent == ROOT / "tests" / "bench" / "data"
    rec.CALLS.clear()
    out, run = bench_run.run_cell(
        {"name": "smoke", "chips": 1}, bench_smoke.CONF, bench_smoke.MIX,
        cfg=bench_smoke.CFG, seed=bench_smoke.SEED, seconds=1.0,
        trace=False, control=True)
    assert out["correct"] is True
    called = [c for c, _ in rec.CALLS]
    assert called[:2] == ["counts", "plain"]
    modes = {m for c, m in rec.CALLS if c == "logits_at"}
    assert modes == {"f32"} | set(bench_run.check.CONTROLS)

    # the readers count through run.counts, which the module made
    rec.CALLS.clear()
    run.trace = SimpleNamespace(busy_s=2.0, op_time=lambda match: 0.5)
    run.traced = (float("-inf"), float("inf"))
    run.peaks = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e8}
    mfu = bench_run.read_metric("mfu", run)
    roof = bench_run.read_metric("paged_attention_roofline", run)
    assert {c for c, _ in rec.CALLS} == {
        "decode_flops", "prefill_flops", "paged_attn_bytes",
        "paged_attn_flops"}
    dense = dense_gqa.counts(bench_smoke.CONF["model"])
    steps = [cs for _, _, cs in run.log.steps]
    assert steps
    f = sum(dense.decode_flops(cs) for cs in steps) + sum(
        dense.prefill_flops(n) for _, _, n in run.log.prefills)
    assert mfu == pytest.approx(100 * f / (2.0 * 1e9))
    ctx = [c for cs in steps for c in cs]
    need = max(dense.paged_attn_bytes(ctx) / 1e8,
               dense.paged_attn_flops(ctx) / 1e9)
    assert roof == pytest.approx(100 * need / 0.5)


def _sums(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(weights._names(p)):
            hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]
            for p, x in flat}


@pytest.mark.parametrize("name", sorted(WEIGHT_SUMS))
def test_dense_weights_are_drawn_as_before(name):
    tree = weights.make(get_smoke_config(name), 2 ** 33 + 3)
    assert _sums(tree) == WEIGHT_SUMS[name]


def test_a_leaf_no_rule_covers_stops_make_with_its_path():
    with pytest.raises(ValueError, match="blocks/scan/0/mixer/A_log"):
        weights.make(get_smoke_config("mamba2-2.7b"), 5)


def test_a_reference_rule_draws_the_leaves_it_names():
    def const(v):
        return lambda key, path, shape: jnp.full(shape, v, jnp.float32)
    rules = {n: const(0.25) for n in ("A_log", "D", "dt_bias", "conv_w",
                                      "conv_b", "in_proj", "out_proj")}
    rules["A_log"] = lambda key, path, shape: jnp.full(
        shape, float(len(path)), jnp.float32)
    tree = weights.make(get_smoke_config("mamba2-2.7b"), 5, rules)
    mixer = tree["blocks"]["scan"]["0"]["mixer"]
    np.testing.assert_array_equal(mixer["A_log"], 5.0)   # its 5-part path
    np.testing.assert_array_equal(np.asarray(mixer["D"]), 0.25)
    assert float(jnp.std(tree["embed"].astype(jnp.float32))) > 0
