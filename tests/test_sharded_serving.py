"""Tensor-parallel serving: sharded == single-shard exact greedy
equivalence, mesh construction, divisibility fallbacks, replica router.

Multi-device cases need a forced multi-device CPU backend
(``XLA_FLAGS=--xla_force_host_platform_device_count=N`` set BEFORE jax
initializes — the CI matrix has a leg for it) and skip gracefully on a
single-device run. The 1x1-mesh case always runs: it exercises the whole
shard_map path — specs, manual rules, boundary placement — on any
backend, so a plain local `pytest` still covers the machinery.

Equivalence is token-for-token under greedy sampling with float32 params:
the TP psum reorders the out-projection accumulation, which fp32 absorbs
below argmax-flip threshold on the smoke configs; the single-shard
baseline and every sharded engine must emit IDENTICAL token streams.
"""
import dataclasses
import warnings

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.launch.mesh import make_host_mesh
from repro.models import api
from repro.parallel.sharding import Rules
from repro.parallel.tp import tp_plan
from repro.runtime.router import ReplicaRouter, make_replicas
from repro.runtime.serving import PagedServingEngine, Request

N_DEV = len(jax.devices())

needs2 = pytest.mark.skipif(
    N_DEV < 2, reason="needs >=2 devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")
needs4 = pytest.mark.skipif(
    N_DEV < 4, reason="needs >=4 devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")


def _cfg(arch="qwen2.5-3b"):
    # fp32 params: greedy equivalence must survive the psum's reordered
    # accumulation without argmax flips
    return dataclasses.replace(get_smoke_config(arch), dtype="float32")


def _params(cfg):
    return api.init_params(cfg, jax.random.key(0))


def _reqs(n=4, max_new=6):
    return [Request(rid=i, prompt=[1 + i, 7, 3 + i, 9, 2], max_new=max_new)
            for i in range(n)]


def _tokens(cfg, params, *, mesh, n=4, max_new=6, **kw):
    eng = PagedServingEngine(cfg, params, slots=3, max_len=64, page_size=8,
                             mesh=mesh, **kw)
    reqs = _reqs(n, max_new)
    eng.run_to_completion(reqs)
    assert all(r.done for r in reqs)
    return [r.generated for r in reqs], eng


# ---------------------------------------------------------------------------
# mesh construction (satellite: make_host_mesh hardening)
# ---------------------------------------------------------------------------


def test_make_host_mesh_rejects_bad_fold():
    with pytest.raises(ValueError) as e:
        make_host_mesh(model=N_DEV + 1)
    msg = str(e.value)
    assert str(N_DEV) in msg and str(N_DEV + 1) in msg  # names n AND model
    with pytest.raises(ValueError):
        make_host_mesh(model=0)
    with pytest.raises(ValueError):
        make_host_mesh(model=3, devices=jax.devices()[:1])


def test_make_host_mesh_devices_override():
    mesh = make_host_mesh(model=1, devices=jax.devices()[:1])
    assert dict(mesh.shape) == {"data": 1, "model": 1}
    assert list(np.ravel(mesh.devices)) == jax.devices()[:1]


@needs4
def test_make_host_mesh_folds_data_axis():
    mesh = make_host_mesh(model=2)
    assert dict(mesh.shape) == {"data": N_DEV // 2, "model": 2}


# ---------------------------------------------------------------------------
# divisibility fallback (satellite: loud replication, serving inherits it)
# ---------------------------------------------------------------------------


def test_rules_divisibility_fallback_no_warning_when_divisible():
    # a 1-wide model axis divides everything: the divisible path must stay
    # silent (the loud path needs >= 2 devices; covered below)
    mesh = make_host_mesh(model=1, devices=jax.devices()[:1])
    rules = Rules(mesh)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        spec = rules.spec((4, 8), "heads,ffn")
    assert spec[0] == "model"                       # heads sharded (1-wide)


@needs2
def test_rules_divisibility_fallback_warns_and_replicates():
    mesh = make_host_mesh(model=2, devices=jax.devices()[:2])
    rules = Rules(mesh)
    with pytest.warns(UserWarning, match="'heads'"):
        spec = rules.spec((5, 6), "heads,ffn")
    assert spec == jax.sharding.PartitionSpec(None, "model")
    with warnings.catch_warnings():                 # once per (instance, axis)
        warnings.simplefilter("error")
        rules.spec((5, 6), "heads,ffn")


@needs2
def test_tp_plan_gqa_coupling_and_moe():
    cfg = _cfg()                                    # heads=4 kv=2 d_ff=128
    mesh = make_host_mesh(model=2, devices=jax.devices()[:2])
    plan = tp_plan(cfg, mesh)
    assert "kv_heads" in plan.sharded_axes and "ffn" in plan.sharded_axes
    assert plan.rules.contract_axes == frozenset({"heads", "ffn"})
    assert tp_plan(cfg, None) is None
    with pytest.raises(ValueError, match="model"):
        tp_plan(cfg, jax.sharding.Mesh(
            np.asarray(jax.devices()[:1]).reshape(1), ("data",)))


@needs4
def test_serving_inherits_fallback_instead_of_crashing():
    """kv_heads=2 on model=4: attention replicates (with a loud warning)
    but the engine still serves, and still matches the baseline."""
    cfg, params = _cfg(), None
    params = _params(cfg)
    base, _ = _tokens(cfg, params, mesh=None)
    with pytest.warns(UserWarning, match="kv_heads"):
        mesh = make_host_mesh(model=4, devices=jax.devices()[:4])
        toks, eng = _tokens(cfg, params, mesh=mesh)
    assert toks == base
    assert "kv_heads" not in eng.tp.sharded_axes    # attention fell back
    assert "ffn" in eng.tp.sharded_axes             # 128 % 4 == 0: ffn kept


# ---------------------------------------------------------------------------
# sharded == single-shard greedy equivalence (the tentpole contract)
# ---------------------------------------------------------------------------


def test_tp1_mesh_matches_plain_engine():
    """A 1x1 mesh runs the FULL shard_map machinery on one device — the
    always-on canary for the TP path (no multi-device backend needed)."""
    cfg, params = _cfg(), None
    params = _params(cfg)
    base, _ = _tokens(cfg, params, mesh=None)
    mesh = make_host_mesh(model=1, devices=jax.devices()[:1])
    toks, eng = _tokens(cfg, params, mesh=mesh)
    assert toks == base
    assert eng.shard_stats()["model_shards"] == 1.0


@needs2
@pytest.mark.parametrize("attn_impl", ["kernel", "gather"])
def test_tp2_exact_equivalence(attn_impl):
    cfg = _cfg()
    params = _params(cfg)
    base, _ = _tokens(cfg, params, mesh=None, attn_impl=attn_impl)
    mesh = make_host_mesh(model=2, devices=jax.devices()[:2])
    toks, eng = _tokens(cfg, params, mesh=mesh, attn_impl=attn_impl)
    assert toks == base
    st = eng.shard_stats()
    assert st["model_shards"] == 2.0
    assert st["peak_pages_per_shard"] == float(eng.alloc.peak_pages)


@needs4
@pytest.mark.slow
@pytest.mark.parametrize("attn_impl", ["kernel", "gather"])
def test_tp4_exact_equivalence(attn_impl):
    cfg = _cfg()
    params = _params(cfg)
    base, _ = _tokens(cfg, params, mesh=None, attn_impl=attn_impl)
    with pytest.warns(UserWarning):                 # kv_heads=2 falls back
        mesh = make_host_mesh(model=4)
        toks, _ = _tokens(cfg, params, mesh=mesh, attn_impl=attn_impl)
    assert toks == base


@needs2
def test_tp2_with_prefix_cache():
    cfg = _cfg()
    params = _params(cfg)
    sys_p = [9, 9, 9, 9, 8, 8, 8, 8, 7, 7]          # shared page + partial
    reqs = lambda: [Request(rid=i, prompt=sys_p + [i + 1, i + 2],  # noqa: E731
                            max_new=5) for i in range(4)]
    base_eng = PagedServingEngine(cfg, params, slots=2, max_len=64,
                                  page_size=8)
    b = reqs()
    base_eng.run_to_completion(b)
    mesh = make_host_mesh(model=2, devices=jax.devices()[:2])
    eng = PagedServingEngine(cfg, params, slots=2, max_len=64, page_size=8,
                             prefix_cache=True, mesh=mesh)
    r = reqs()
    eng.run_to_completion(r)
    assert [x.generated for x in r] == [x.generated for x in b]
    assert eng.prefix_stats()["prefill_tokens_saved"] > 0  # sharing happened


@needs2
@pytest.mark.slow
def test_tp2_with_speculative_decode():
    cfg = _cfg()
    params = _params(cfg)
    # repetitive prompts so the n-gram drafter actually lands accepts
    reqs = lambda: [Request(rid=i, prompt=[5, 6, 5, 6, 5, 6, 5],  # noqa: E731
                            max_new=8) for i in range(3)]
    base_eng = PagedServingEngine(cfg, params, slots=3, max_len=64,
                                  page_size=8)
    b = reqs()
    base_eng.run_to_completion(b)
    mesh = make_host_mesh(model=2, devices=jax.devices()[:2])
    eng = PagedServingEngine(cfg, params, slots=3, max_len=64, page_size=8,
                             spec_k=3, mesh=mesh)
    r = reqs()
    eng.run_to_completion(r)
    assert [x.generated for x in r] == [x.generated for x in b]


@needs2
@pytest.mark.slow
def test_tp2_preemption_resume():
    """A page pool too small for all requests forces preemption; the
    preempted request resumes by re-prefill on SHARDED pools and must
    still match the unsharded engine run under the same pressure."""
    cfg = _cfg()
    params = _params(cfg)

    def run(mesh):
        eng = PagedServingEngine(cfg, params, slots=3, max_len=64,
                                 page_size=8, num_pages=5, mesh=mesh)
        reqs = [Request(rid=i, prompt=[1 + i, 7, 3 + i, 9, 2, 4, 6],
                        max_new=10) for i in range(3)]
        eng.run_to_completion(reqs)
        return reqs

    base = run(None)
    shard = run(make_host_mesh(model=2, devices=jax.devices()[:2]))
    assert sum(r.preemptions for r in base) > 0     # pressure was real
    assert [r.generated for r in shard] == [r.generated for r in base]
    assert [r.preemptions for r in shard] == [r.preemptions for r in base]


@needs2
@pytest.mark.slow
@pytest.mark.parametrize("arch", ["recurrentgemma-9b", "mamba2-2.7b"])
def test_tp2_hybrid_stacks(arch):
    """Windowed + recurrent stacks: mixer state replicates, whatever can
    shard shards (rgemma smoke kv_heads=1 -> attention falls back), and
    outputs still match token-for-token."""
    cfg = _cfg(arch)
    params = _params(cfg)
    base, _ = _tokens(cfg, params, mesh=None)
    mesh = make_host_mesh(model=2, devices=jax.devices()[:2])
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", UserWarning)   # kv fallback ok
        toks, _ = _tokens(cfg, params, mesh=mesh)
    assert toks == base


# ---------------------------------------------------------------------------
# sampled equivalence: decode policies ride OUTSIDE shard_map, so the
# per-request PRNG sees identical logits and keys at every shard count
# ---------------------------------------------------------------------------


def _sampled_tokens(cfg, params, *, mesh, n=4, max_new=6):
    from repro.runtime.sampling import SamplingParams
    eng = PagedServingEngine(cfg, params, slots=3, max_len=64, page_size=8,
                             mesh=mesh)
    reqs = _reqs(n, max_new)
    for i, r in enumerate(reqs):
        r.params = SamplingParams(temperature=0.9, top_k=6, top_p=0.9,
                                  seed=100 + i)
    eng.run_to_completion(reqs)
    assert all(r.done for r in reqs)
    return [r.generated for r in reqs]


def test_tp1_mesh_sampled_matches_plain():
    cfg = _cfg()
    params = _params(cfg)
    base = _sampled_tokens(cfg, params, mesh=None)
    mesh = make_host_mesh(model=1, devices=jax.devices()[:1])
    assert _sampled_tokens(cfg, params, mesh=mesh) == base
    # distinct per-request seeds really produced distinct streams
    assert len({tuple(t) for t in base}) == len(base)


@needs2
def test_tp2_sampled_equivalence():
    cfg = _cfg()
    params = _params(cfg)
    base = _sampled_tokens(cfg, params, mesh=None)
    mesh = make_host_mesh(model=2, devices=jax.devices()[:2])
    assert _sampled_tokens(cfg, params, mesh=mesh) == base


@needs4
@pytest.mark.slow
def test_tp4_sampled_equivalence():
    cfg = _cfg()
    params = _params(cfg)
    base = _sampled_tokens(cfg, params, mesh=None)
    with pytest.warns(UserWarning):                 # kv_heads=2 falls back
        mesh = make_host_mesh(model=4)
        toks = _sampled_tokens(cfg, params, mesh=mesh)
    assert toks == base


# ---------------------------------------------------------------------------
# replica router
# ---------------------------------------------------------------------------


def test_router_single_replica_matches_engine():
    cfg = _cfg()
    params = _params(cfg)
    base, _ = _tokens(cfg, params, mesh=None, n=5)
    rr = make_replicas(cfg, params, replicas=1, slots=3, max_len=64,
                       page_size=8)
    reqs = _reqs(5)
    rr.run_to_completion(reqs)
    assert [r.generated for r in reqs] == base
    assert rr.stats()["routed"] == [5]


def test_router_validates():
    cfg = _cfg()
    params = _params(cfg)
    with pytest.raises(ValueError, match="device"):
        make_replicas(cfg, params, replicas=N_DEV + 1)
    with pytest.raises(ValueError, match="policy"):
        ReplicaRouter([object()], policy="round_robin")
    with pytest.raises(ValueError):
        ReplicaRouter([])


@needs2
@pytest.mark.parametrize("policy", ["hash", "least_loaded"])
def test_router_replicas_match_baseline(policy):
    cfg = _cfg()
    params = _params(cfg)
    base, _ = _tokens(cfg, params, mesh=None, n=6)
    rr = make_replicas(cfg, params, replicas=2, slots=3, max_len=64,
                       page_size=8, policy=policy)
    reqs = _reqs(6)
    rr.run_to_completion(reqs)
    assert [r.generated for r in reqs] == base
    st = rr.stats()
    assert sum(st["routed"]) == 6 and min(st["routed"]) > 0
    assert len(st["peak_pages_per_shard"]) == 2


@needs2
def test_router_replicas_own_their_devices():
    """One-device replicas hold weights, pool and bookkeeping on their own
    device, before and after serving (not all on the default device)."""
    cfg = _cfg()
    params = _params(cfg)
    rr = make_replicas(cfg, params, replicas=2, slots=3, max_len=64,
                       page_size=8)
    rr.run_to_completion(_reqs(4))
    for i, e in enumerate(rr.engines):
        dev = jax.devices()[i]
        for tree in (e.params, e.cache, e.block_table, e.pos):
            assert {d for leaf in jax.tree.leaves(tree)
                    for d in leaf.devices()} == {dev}


@needs4
@pytest.mark.slow
def test_router_tp_replicas_compose():
    """2 replicas x 2 shards on 4 devices: DP and TP together."""
    cfg = _cfg()
    params = _params(cfg)
    base, _ = _tokens(cfg, params, mesh=None, n=6)
    rr = make_replicas(cfg, params, replicas=2, model=2, slots=3,
                       max_len=64, page_size=8)
    reqs = _reqs(6)
    rr.run_to_completion(reqs)
    assert [r.generated for r in reqs] == base
    assert rr.stats()["model_shards"] == [2.0, 2.0]
