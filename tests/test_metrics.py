"""Unified metrics surface (ISSUE 8): one key set across every engine
configuration, sane latency/utilization numbers, a single warm-up reset
point, and engine-driven traces that pass schema validation."""
import jax
import pytest

from repro.configs import get_smoke_config
from repro.models import api
from repro.runtime.scheduler import Scheduler
from repro.runtime.serving import (DenseServingEngine, PagedServingEngine,
                                   Request)
from repro.runtime.trace import NULL_TRACER, Tracer, validate_trace

SLOTS, MAX_LEN, MAX_NEW = 2, 32, 3


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke_config("qwen2.5-3b")
    return cfg, api.init_params(cfg, jax.random.key(0))


def _reqs(n=3, max_new=MAX_NEW):
    return [Request(rid=i, prompt=[2 + 3 * i + j for j in range(2 + 2 * i)],
                    max_new=max_new)
            for i in range(n)]


def _run(eng, n=3):
    reqs = _reqs(n)
    sched = Scheduler(eng)
    for r in reqs:
        sched.add(r)
    sched.drain(max_steps=200)
    return reqs


def _build_all(cfg, params):
    mk = dict(slots=SLOTS, max_len=MAX_LEN)
    return {
        "paged": PagedServingEngine(cfg, params, **mk),
        "paged+prefix": PagedServingEngine(cfg, params, prefix_cache=True,
                                           **mk),
        "paged+spec": PagedServingEngine(cfg, params, spec_k=3, **mk),
        "paged+tier": PagedServingEngine(cfg, params, host_tier=True, **mk),
        "dense": DenseServingEngine(cfg, params, **mk),
    }


@pytest.fixture(scope="module")
def driven(model):
    cfg, params = model
    engines = _build_all(cfg, params)
    for eng in engines.values():
        _run(eng)
    return engines


def test_metrics_key_set_identical_across_configs(driven):
    """The contract dashboards and CSV columns ride on: every engine and
    every feature combination reports the SAME flat key set — subsystems
    that are off report zeros, never missing keys."""
    key_sets = {name: set(e.metrics().keys()) for name, e in driven.items()}
    ref_name, ref = next(iter(key_sets.items()))
    for name, ks in key_sets.items():
        assert ks == ref, (
            f"{name} metrics keys diverge from {ref_name}: "
            f"only-in-{name}={sorted(ks - ref)}, "
            f"missing={sorted(ref - ks)}")
    # the namespaces the consolidation promises are all present
    for ns in ("engine.", "latency.", "util.", "pool.", "spec.",
               "prefix.", "tier.", "shard."):
        assert any(k.startswith(ns) for k in ref), f"no {ns}* keys"
    # the wall split the CI bench gate reads, and nothing more
    assert {k for k in ref if k.startswith("util.")} == {
        "util.step_wall_s", "util.tick_wall_s", "util.temporal"}


def test_subsystem_stats_key_sets_stable(driven):
    """Each ``*_stats()`` method returns the same keys whether its
    subsystem is on or off (zeros when off)."""
    for meth in ("pool_stats", "spec_stats", "prefix_stats", "tier_stats",
                 "shard_stats"):
        sets = {}
        for name, eng in driven.items():
            st = getattr(eng, meth)()
            sets[name] = set(st.keys()) if isinstance(st, dict) \
                else set(vars(st).keys())
        ref = sets["paged"]
        for name, ks in sets.items():
            assert ks == ref, f"{meth} keys differ: paged vs {name}"
    # off-configs really report zeros, not stale values
    plain = driven["paged"]
    assert plain.prefix_stats()["hits"] == 0
    assert plain.tier_stats()["host_tier"] == 0.0
    assert driven["dense"].spec_stats()["spec_drafted"] == 0.0


def test_latency_and_utilization_sane(driven):
    for name, eng in driven.items():
        m = eng.metrics()
        assert m["latency.requests"] == 3.0, name
        assert m["latency.ttft_p50_s"] > 0.0, name
        assert m["latency.ttft_p95_s"] >= m["latency.ttft_p50_s"], name
        # every request emitted MAX_NEW >= 2 tokens, so TPOT has samples
        assert m["latency.tpot_p50_s"] > 0.0, name
        assert m["latency.tpot_p95_s"] >= m["latency.tpot_p50_s"], name
        # temporal utilization is a ratio of nested wall intervals
        assert 0.0 < m["util.temporal"] <= 1.0, name
        assert m["util.step_wall_s"] <= m["util.tick_wall_s"], name
        # the first token per request comes out of prefill, the rest out
        # of decode steps
        assert m["engine.decoded_tokens"] >= 3 * (MAX_NEW - 1), name


def test_ttft_includes_queue_wait(model):
    """Arrival is stamped at Scheduler.add (enqueue), not at admission:
    a request stuck behind a full engine accrues TTFT while it queues."""
    cfg, params = model
    eng = PagedServingEngine(cfg, params, slots=1, max_len=MAX_LEN)
    reqs = _reqs(3)
    sched = Scheduler(eng)
    for r in reqs:
        sched.add(r)                      # 3 requests, 1 slot: 2 queue
    sched.drain(max_steps=200)
    m = eng.metrics()
    ttfts = sorted(eng.first_token_at[r.rid] - eng._arrival_at[r.rid]
                   for r in reqs)
    # the queued requests waited for a predecessor's full generation
    assert ttfts[-1] > ttfts[0]
    assert m["latency.ttft_p95_s"] >= m["latency.ttft_p50_s"]


def test_reset_metrics_is_the_single_reset_point(model):
    cfg, params = model
    eng = PagedServingEngine(cfg, params, slots=SLOTS, max_len=MAX_LEN,
                             prefix_cache=True)
    _run(eng)
    assert eng.decode_steps > 0 and eng.prefilled_tokens > 0
    traces_before = eng.prefill_traces
    eng.reset_metrics()
    m = eng.metrics()
    assert m["engine.decode_steps"] == 0.0
    assert m["engine.decoded_tokens"] == 0.0
    assert m["latency.requests"] == 0.0
    assert m["latency.ttft_p50_s"] == 0.0
    assert m["util.step_wall_s"] == 0.0 and m["util.temporal"] == 0.0
    # subsystem counters reset through the same call
    assert eng.prefilled_tokens == 0 and eng.prompt_tokens == 0
    assert eng.alloc.share_events == 0
    assert eng.prefix_stats()["lookups"] == 0
    # lifetime facts survive: jit retrace identity is not a per-phase rate
    assert eng.prefill_traces == traces_before
    # and the engine still serves correctly after a reset
    reqs = _run(eng)
    assert all(len(r.generated) == MAX_NEW for r in reqs)
    assert eng.metrics()["latency.requests"] == 3.0


def test_engine_without_tracer_uses_null_tracer(driven):
    for eng in driven.values():
        assert eng.trace is NULL_TRACER


@pytest.fixture(scope="module", params=["paged", "dense"])
def traced(request, model):
    """(kind, tracer, requests) of one scheduler-driven run with an
    enabled tracer."""
    cfg, params = model
    tr = Tracer(enabled=True)
    cls = PagedServingEngine if request.param == "paged" \
        else DenseServingEngine
    eng = cls(cfg, params, slots=SLOTS, max_len=MAX_LEN, tracer=tr)
    return request.param, tr, _run(eng)


def _pairs(evs, name):
    """[(id, ph)] of the async events called ``name``, in order."""
    return [(e["id"], e["ph"]) for e in evs
            if e.get("ph") in ("b", "e") and e["name"] == name]


def test_engine_run_produces_valid_trace(traced):
    kind, tr, reqs = traced
    obj = tr.to_dict()
    assert validate_trace(obj) == []
    evs = obj["traceEvents"]
    spans = {e["name"] for e in evs if e.get("ph") == "X"}
    assert {"admit", "prefill_dispatch", "prefill_sync", "decode_tick",
            "operands", "device_dispatch", "host_sync", "emit", "tick",
            "admit_loop"} <= spans
    instants = {e["name"] for e in evs if e.get("ph") == "i"}
    assert "first_token" in instants
    # one async begin/end pair per request lifecycle
    life = _pairs(evs, "request")
    begins = [i for i, ph in life if ph == "b"]
    ends = [i for i, ph in life if ph == "e"]
    assert sorted(begins) == sorted(ends) == [str(r.rid) for r in reqs]
    if kind == "paged":
        assert any(e.get("ph") == "C" and e["name"] == "pool_pages"
                   for e in evs)


def _ticks(evs):
    """Each decode_tick with the names of the engine spans directly
    inside it, in order."""
    engine = next(e["tid"] for e in evs if e.get("ph") == "M"
                  and e["args"]["name"] == "engine")
    spans = sorted(((e["ts"], -e["dur"], e["name"]) for e in evs
                    if e.get("ph") == "X" and e["tid"] == engine))
    out = []
    for ts, neg, name in spans:
        if name != "decode_tick":
            continue
        end = ts - neg
        inner = [(s, s - n, m) for s, n, m in spans
                 if ts <= s and s - n <= end and m != "decode_tick"]
        # direct children: those no other span inside the tick encloses
        kids = [m for s, e, m in inner
                if not any(s2 <= s and e <= e2 and (s2, e2) != (s, e)
                           for s2, e2, _ in inner)]
        out.append(kids)
    return out


def test_decode_tick_children_are_the_phases(traced):
    """Every decode tick is cut into the engine's host phases, nested in
    it and in this order (the paged engine tops up pages first)."""
    kind, tr, reqs = traced
    ticks = _ticks(tr.to_dict()["traceEvents"])
    want = (["ensure_capacity"] if kind == "paged" else []) + [
        "operands", "device_dispatch", "host_sync", "emit"]
    assert ticks and all(kids == want for kids in ticks), ticks
    if kind == "paged":
        live = [e["args"]["live"] for e in tr.events()
                if e["ph"] == "C" and e["name"] == "pool_pages"]
        assert len(live) == len(ticks)
        assert set(live) <= set(range(1, SLOTS + 1)) and max(live) == SLOTS


def test_queued_pairs_close_once_per_admission(traced):
    kind, tr, reqs = traced
    evs = tr.to_dict()["traceEvents"]
    queued = _pairs(evs, "queued")
    for r in reqs:
        assert [ph for i, ph in queued if i == str(r.rid)] == ["b", "e"]
    # each closes where its admission starts
    admits = {e["args"]["rid"]: e["ts"] for e in evs
              if e.get("ph") == "X" and e["name"] == "admit"}
    ends = {e["id"]: e["ts"] for e in evs
            if e.get("ph") == "e" and e["name"] == "queued"}
    for r in reqs:
        assert ends[str(r.rid)] <= admits[r.rid]


def test_queued_pairs_reopen_after_preemption(model):
    """4 pages of 4 tokens: decode growth preempts a request, which
    queues again and closes a second ``queued`` pair when it resumes."""
    cfg, params = model
    tr = Tracer(enabled=True)
    eng = PagedServingEngine(cfg, params, slots=2, max_len=32, page_size=4,
                             num_pages=4, tracer=tr)
    reqs = [Request(rid=0, prompt=[5, 4, 3, 2, 1, 6, 7], max_new=8),
            Request(rid=1, prompt=[1, 2, 3, 4, 5, 6], max_new=8)]
    sched = Scheduler(eng)
    for r in reqs:
        sched.add(r)
    sched.drain(max_steps=400)
    assert sched.preempted >= 1
    obj = tr.to_dict()
    assert validate_trace(obj) == []
    queued = _pairs(obj["traceEvents"], "queued")
    for r in reqs:
        mine = [ph for i, ph in queued if i == str(r.rid)]
        assert mine == ["b", "e"] * (1 + r.preemptions)
