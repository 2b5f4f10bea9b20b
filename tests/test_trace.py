"""Tracer unit tests: Chrome Trace Event schema, ring-buffer bounds, the
disabled-tracer no-op contract, and an enabled tracer's spans on the JAX
profiler's timeline."""
import glob
import json
import sys
from pathlib import Path

from repro.runtime import trace as trace_mod
from repro.runtime.trace import (NOOP_SPAN, NULL_TRACER, Tracer,
                                 default_tracer, percentile,
                                 set_default_tracer, validate_trace)

ROOT = Path(__file__).resolve().parents[1]


# -- schema / export ------------------------------------------------------

def test_export_validates_and_round_trips(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("decode_tick"):
        with tr.span("device_dispatch"):
            pass
        with tr.span("host_sync"):
            pass
    tr.instant("first_token", args={"rid": 0})
    tr.counter("pool_pages", {"allocated": 3.0, "free": 5.0})
    tr.begin_async("request", 0)
    tr.end_async("request", 0)
    path = tmp_path / "trace.json"
    tr.export(str(path))
    obj = json.loads(path.read_text())
    assert validate_trace(obj) == []
    evs = obj["traceEvents"]
    # metadata rows label the process and every tid used
    assert any(e["ph"] == "M" and e["name"] == "process_name" for e in evs)
    tnames = {e["args"]["name"] for e in evs
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"engine", "requests"} <= tnames
    names = {e["name"] for e in evs if e["ph"] == "X"}
    assert {"decode_tick", "device_dispatch", "host_sync"} <= names
    assert obj["otherData"]["dropped_events"] == 0


def test_spans_record_at_exit_with_nonneg_duration():
    tr = Tracer(enabled=True)
    with tr.span("outer"):
        assert tr.events() == []          # complete events land on EXIT
        with tr.span("inner"):
            pass
    inner, outer = tr.events()
    assert inner["name"] == "inner" and outer["name"] == "outer"
    assert inner["ph"] == outer["ph"] == "X"
    for ev in (inner, outer):
        assert isinstance(ev["ts"], int) and ev["ts"] >= 0
        assert isinstance(ev["dur"], int) and ev["dur"] >= 0
    # the child is contained in the parent on the same tid
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]


def test_phase_walls_aggregates_by_name():
    tr = Tracer(enabled=True)
    for _ in range(3):
        with tr.span("tick"):
            pass
    walls = tr.phase_walls()
    assert walls["tick"][0] == 3
    assert walls["tick"][1] >= 0.0
    assert "tick" in tr.format_phase_walls()


# -- ring buffer ----------------------------------------------------------

def test_ring_buffer_drops_oldest_without_corrupting_output(tmp_path):
    tr = Tracer(enabled=True, capacity=4)
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    assert len(tr.events()) == 4
    assert tr.dropped_events == 6
    assert [e["name"] for e in tr.events()] == ["s6", "s7", "s8", "s9"]
    path = tmp_path / "trace.json"
    tr.export(str(path))
    obj = json.loads(path.read_text())
    assert validate_trace(obj) == []      # truncated trace is still valid
    assert obj["otherData"]["dropped_events"] == 6


def test_dropped_async_begin_does_not_fail_validation():
    tr = Tracer(enabled=True, capacity=2)
    tr.begin_async("request", 0)
    with tr.span("a"):                    # evicts the 'b' row
        pass
    with tr.span("b"):
        pass
    tr.end_async("request", 0)            # orphaned 'e', but declared
    assert tr.dropped_events > 0
    assert validate_trace(tr.to_dict()) == []


# -- disabled tracer is a true no-op --------------------------------------

def test_disabled_tracer_allocates_nothing():
    tr = Tracer(enabled=False)
    assert not tr                          # guards arg-dict construction
    # the SAME shared context manager object every call: no per-call span
    assert tr.span("x") is NOOP_SPAN
    assert tr.span("y", tid="tier") is NOOP_SPAN
    with tr.span("x"):
        pass
    tr.instant("i")
    tr.counter("c", {"v": 1.0})
    tr.begin_async("request", 1)
    tr.end_async("request", 1)
    assert tr.events() == []
    assert tr.events_recorded == 0
    assert NULL_TRACER.span("z") is NOOP_SPAN


def test_disabled_tracer_enters_no_profiler_annotation(monkeypatch):
    labels = []
    real = trace_mod._annotation

    def counting(label):
        labels.append(label)
        return real(label)

    monkeypatch.setattr(trace_mod, "_annotation", counting)
    off = Tracer(enabled=False)
    with off.span("decode_tick"):
        with off.span("tick", tid="sched"):
            pass
    assert labels == []
    on = Tracer(enabled=True)
    with on.span("decode_tick"):
        with on.span("tick", tid="sched"):
            pass
    assert labels == ["engine.decode_tick", "sched.tick"]


def test_enabled_spans_land_in_the_profiler_trace(tmp_path):
    """Under the JAX profiler an enabled tracer's spans are host events
    named ``<tid>.<name>``, never one of the names the benchmark's trace
    reader keeps for its own spans."""
    import jax
    from jax.profiler import ProfileData
    sys.path.insert(0, str(ROOT))
    from bench import xplane
    tr = Tracer(enabled=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("tick", tid="sched"):
            with tr.span("decode_tick"):
                with tr.span("step"):      # a bench span name, prefixed
                    jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert {"sched.tick", "engine.decode_tick", "engine.step"} <= names
    assert not names & (set(xplane.HOST_SPANS) | {xplane.WINDOW})


def test_rounding_keeps_children_inside_parents(monkeypatch):
    """Both ends of a span are floored to the µs, so a child that ends in
    its parent's last microsecond still nests."""
    ns = iter([0, 900, 1000, 2000, 2000])
    monkeypatch.setattr(trace_mod.time, "perf_counter_ns", lambda: next(ns))
    tr = Tracer(enabled=True)
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    inner, outer = tr.events()
    assert (outer["ts"], outer["dur"]) == (0, 2)
    assert (inner["ts"], inner["dur"]) == (1, 1)
    assert validate_trace(tr.to_dict()) == []


def test_epoch_and_end_async_at_a_given_time():
    tr = Tracer(enabled=True)
    t = tr.now()
    tr.begin_async("queued", 3)
    tr.end_async("queued", 3, ts=t)
    b, e = tr.events()
    assert e["ts"] == t <= b["ts"]
    # events convert back to time.perf_counter seconds through epoch_ns
    import time
    assert abs(tr.epoch_ns * 1e-9 + t * 1e-6 - time.perf_counter()) < 1.0


def test_default_tracer_install_and_restore():
    assert default_tracer() is NULL_TRACER
    tr = Tracer(enabled=True)
    set_default_tracer(tr)
    try:
        assert default_tracer() is tr
    finally:
        set_default_tracer(None)
    assert default_tracer() is NULL_TRACER


# -- validator catches malformed traces -----------------------------------

def test_validator_rejects_bad_top_level():
    assert validate_trace([]) != []
    assert validate_trace({"events": []}) != []
    assert validate_trace({"traceEvents": "nope"}) != []


def test_validator_rejects_bad_events():
    base = {"pid": 0, "tid": 0, "ts": 0}
    bad = [
        dict(base, ph="Z", name="x"),                      # unknown phase
        dict(base, ph="X", name="x"),                      # X without dur
        dict(base, ph="X", name="x", dur=-1),              # negative dur
        dict(base, ph="X", dur=1),                         # X without name
        {"ph": "X", "name": "x", "ts": 0, "dur": 1, "tid": 0},  # no pid
        dict(base, ph="C", name="c"),                      # C without args
        dict(base, ph="e", name="r"),                      # e without id/cat
    ]
    for ev in bad:
        assert validate_trace({"traceEvents": [ev]}) != [], ev


def test_validator_rejects_partial_overlap_on_one_track():
    evs = [{"ph": "X", "name": "a", "ts": 0, "dur": 10, "pid": 0, "tid": 0},
           {"ph": "X", "name": "b", "ts": 5, "dur": 10, "pid": 0, "tid": 0}]
    errs = validate_trace({"traceEvents": evs})
    assert any("must nest" in e for e in errs)
    # same intervals on DIFFERENT tracks are fine
    evs[1]["tid"] = 1
    assert validate_trace({"traceEvents": evs}) == []


def test_validator_rejects_unmatched_async_end_when_nothing_dropped():
    evs = [{"ph": "e", "name": "request", "cat": "request", "id": "7",
            "ts": 0, "pid": 0, "tid": 0}]
    errs = validate_trace({"traceEvents": evs})
    assert any("async end without matching begin" in e for e in errs)


# -- percentile helper ----------------------------------------------------

def test_percentile():
    assert percentile([], 0.5) == 0.0
    assert percentile([3.0], 0.95) == 3.0
    xs = [1.0, 2.0, 3.0, 4.0]
    assert percentile(xs, 0.0) == 1.0
    assert percentile(xs, 1.0) == 4.0
    assert percentile(xs, 0.5) == 2.5
    assert percentile(list(reversed(xs)), 0.5) == 2.5   # sorts internally
