"""The engine-phase readers (``bench/spans.py`` and the five metrics that
read it) on a made-up run: hand-made tracer events on the host's clock,
a hand-made device trace on a clock 4900 s ahead, and idle time that has
to land in the right phase."""
import importlib.util
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import loop, spans, xplane  # noqa: E402
from bench.readers import Run  # noqa: E402

EPOCH = 100.0                   # the tracer's epoch, perf_counter seconds
OFFSET = 4900.0                 # trace clock - perf_counter clock
TIDS = {"engine": 0, "sched": 1, "requests": 2}
READERS = ("step_prep_ms", "step_emit_ms", "step_idle_ms",
           "decode_batch_mean", "sched_wait_p80_s")


def reader(name):
    sp = importlib.util.spec_from_file_location(
        name, ROOT / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod.read


def _us(t):
    return round((t - EPOCH) * 1e6)


def X(name, start, end, tid="engine"):
    return {"ph": "X", "name": name, "cat": "serve", "pid": 0,
            "tid": TIDS[tid], "ts": _us(start), "dur": _us(end) - _us(start)}


def A(ph, name, rid, t):
    return {"ph": ph, "name": name, "cat": "request", "id": str(rid),
            "pid": 0, "tid": TIDS["requests"], "ts": _us(t)}


def C(t, live):
    return {"ph": "C", "name": "pool_pages", "cat": "serve", "pid": 0,
            "tid": 0, "ts": _us(t),
            "args": {"allocated": 9.0, "free": 1.0, "live": live}}


def tick(t0):
    """A 200-ms decode tick at ``t0``: 10 ms each of pages, operands and
    dispatch, 150 ms of sync, 20 ms of emit (children first, as a tracer
    records them: at exit)."""
    return [X("ensure_capacity", t0, t0 + .01), X("operands", t0 + .01,
                                                  t0 + .02),
            X("device_dispatch", t0 + .02, t0 + .03),
            X("host_sync", t0 + .03, t0 + .18), X("emit", t0 + .18, t0 + .2),
            X("decode_tick", t0, t0 + .2)]


EVENTS = (
    [{"ph": "M", "name": "thread_name", "pid": 0, "tid": v,
      "args": {"name": k}} for k, v in TIDS.items()]
    + [A("b", "queued", 7, 50.0), A("e", "queued", 7, 50.1)]   # warm-up
    + [C(99.0, 32.0)]                                  # before the window
    + [A("b", "queued", 7, 100.2)] + tick(100.1) + [C(100.3, 3.0)]
    + [X("prefill_sync", 100.44, 100.45), X("admit", 100.35, 100.45),
       X("tick", 100.34, 100.46, tid="sched"), A("e", "queued", 7, 100.35)]
    + tick(100.5) + [C(100.7, 5.0), A("b", "queued", 8, 100.6),
                     A("e", "queued", 8, 100.9),
                     A("b", "queued", 8, 100.95),     # preempted, resumed
                     A("e", "queued", 8, 100.99)])


class FakeTracer:
    enabled = True
    epoch_ns = int(EPOCH * 1e9)

    def __bool__(self):
        return self.enabled

    def to_dict(self):
        return {"traceEvents": EVENTS}


def make_run():
    T = OFFSET
    ops = [("jit_step(3)", T + 100.125, T + 100.275),
           ("jit_pf(1)", T + 100.36, T + 100.44),
           ("jit_step(3)", T + 100.525, T + 100.675)]
    trace = xplane.Trace(T + 100.0, T + 101.0, 1, modules=ops, ops=ops,
                         spans=[], busy_s=0.38, _first_chip=ops)
    log = loop.Log()
    for rid, due in ((7, 100.2), (8, 100.6), (9, 99.0)):
        log.recs[rid] = loop.Rec(rid, 10, 5, due, added=due)
    return Run(setup_s=1.0, t0=100.0, end=101.0, drained=101.0, log=log,
               counts=None, peaks={}, trace=trace, traced=(100.0, 101.0))


@pytest.fixture
def tracer(monkeypatch):
    fake = FakeTracer()
    monkeypatch.setattr(spans, "default_tracer", lambda: fake)
    return fake


def test_readers_on_a_made_up_run(tracer):
    run = make_run()
    got = {name: reader(name)(run) for name in READERS}
    assert got["step_prep_ms"] == pytest.approx(30.0)
    assert got["step_emit_ms"] == pytest.approx(20.0)
    # per tick: 25 ms before the step program starts, 5 + 20 ms after
    assert got["step_idle_ms"] == pytest.approx(50.0)
    assert got["decode_batch_mean"] == pytest.approx(4.0)
    # first waits 0.15 and 0.3 s; the warm-up pair and the resumption
    # are not the admission
    assert got["sched_wait_p80_s"] == pytest.approx(0.15 + 0.8 * 0.15)


def test_idle_lands_in_the_innermost_phase(tracer, capsys):
    p = spans.phases(make_run())
    want = {"engine.ensure_capacity": 0.02, "engine.operands": 0.02,
            "engine.device_dispatch": 0.01, "engine.host_sync": 0.01,
            "engine.emit": 0.04, "engine.admit": 0.01,
            "engine.prefill_sync": 0.01, "sched.tick": 0.02,
            spans.OUTSIDE: 0.1 + 0.04 + 0.04 + 0.3}
    assert set(p.idle) <= set(want)
    for k, v in want.items():
        assert p.idle.get(k, 0.0) == pytest.approx(v, abs=1e-9), k
    assert sum(p.idle.values()) == pytest.approx(1.0 - 0.38)
    err = capsys.readouterr().err
    assert "2 of 2 jit_step runs start inside" in err
    assert "idle engine.emit: " in err


def test_a_clock_mapped_the_wrong_way_shows_in_the_check(tracer, capsys):
    """The device trace read as if on the host's clock: the decode
    program's runs fall outside every tick, and the check says so."""
    run = make_run()
    run.traced = (100.0 + OFFSET, 101.0 + OFFSET)
    assert spans.phases(run).step_idle_s is None      # no tick in window
    run = make_run()
    run.trace.start -= 0.2                  # host spans mapped 200 ms early
    run.trace.end -= 0.2
    spans.phases(run)
    assert "0 of 2 jit_step runs start inside" in capsys.readouterr().err


@pytest.mark.parametrize("what", ["disabled", "no epoch"])
def test_nothing_recorded_reads_none(monkeypatch, what):
    """A run whose program recorded nothing: the tracer was off, or the
    program's tracer has no ``epoch_ns`` (an older program)."""
    fake = FakeTracer()
    if what == "disabled":
        fake.enabled = False
    else:
        monkeypatch.delattr(FakeTracer, "epoch_ns")
    monkeypatch.setattr(spans, "default_tracer", lambda: fake)
    run = make_run()
    assert all(reader(name)(run) is None for name in READERS)


def test_innermost_and_overlap():
    pieces = spans.innermost([("a", 0.0, 10.0), ("b", 2.0, 4.0),
                              ("c", 3.0, 4.0), ("d", 6.0, 7.0),
                              ("e", 12.0, 13.0)])
    assert pieces == [("a", 0.0, 2.0), ("b", 2.0, 3.0), ("c", 3.0, 4.0),
                      ("a", 4.0, 6.0), ("d", 6.0, 7.0), ("a", 7.0, 10.0),
                      ("e", 12.0, 13.0)]
    assert spans.overlap([(0.0, 1.0), (2.0, 5.0)],
                         [(0.5, 2.5), (4.0, 9.0)]) == pytest.approx(2.0)


def test_smoke_run_with_the_engine_tracer():
    """``bench/phases.py``'s run at smoke size on the CPU (no device
    trace there): the engine's own spans feed the readers that need no
    trace."""
    from bench import phases
    from repro.runtime.trace import Tracer, set_default_tracer
    sys.path.insert(0, str(ROOT / "tests" / "bench"))
    import bench_smoke
    set_default_tracer(Tracer(enabled=True))
    try:
        out = bench_smoke.run(metrics=phases.PHASE_METRICS)
    finally:
        set_default_tracer(None)
    got = out["metrics"]
    assert out["correct"]
    assert set(got) == set(READERS) - {"step_idle_ms"}
    assert got["step_prep_ms"]["value"] > 0
    assert got["step_emit_ms"]["value"] > 0
    assert 1 <= got["decode_batch_mean"]["value"] <= 4       # 4 slots
    assert got["sched_wait_p80_s"]["value"] >= 0
