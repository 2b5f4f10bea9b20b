"""The harness's plumbing that needs no engine: server flags from a
configuration, metric readers found by name, and the knee rule of the
open-loop sweep."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import calibrate, run  # noqa: E402
from bench.readers import program_share  # noqa: E402


def test_server_flags_follow_add_server_flags_names():
    assert run.server_flags({"slots": 32, "max_len": 2048,
                             "prefix_cache": True, "host_tier": False}) == \
        ["--slots=32", "--max-len=2048", "--prefix-cache"]


@pytest.mark.parametrize("conf", sorted((ROOT / "bench" / "configs").glob(
    "*.json")), ids=lambda p: p.stem)
def test_every_configuration_parses_as_server_flags(conf):
    import argparse
    import json
    from repro.launch import serve
    ap = argparse.ArgumentParser()
    serve.add_server_flags(ap)
    flags = json.loads(conf.read_text())["deployment"]["flags"]
    args = ap.parse_args(run.server_flags(flags))
    assert args.slots == flags["slots"] and args.max_len == flags["max_len"]


def test_a_part_name_is_read_by_its_metric_reader():
    r = SimpleNamespace(setup_s=12.5)
    assert run.read_metric("setup_s", r) == 12.5
    assert run.read_metric("setup_s.anything", r) == 12.5


def test_every_metric_in_the_benchmark_has_a_reader():
    spec = run.load_json(ROOT / "BENCHMARK.json")
    for m in spec["end_to_end"] + spec["per_layer"]:
        name = m["name"]
        found = [p for p in (name, name.split(".")[0])
                 if (ROOT / "bench" / "metrics" / f"{p}.py").exists()]
        assert found, name


def test_kv_pages_held_share_reads_steps_in_the_window():
    log = SimpleNamespace(pools=[(0.5, 4, 2, 8), (1.5, 4, 6, 8),
                                 (9.0, 4, 8, 8), (2.0, 4, 0, 0)])
    r = SimpleNamespace(t0=1.0, end=5.0, log=log)
    assert run.read_metric("kv_pages_held_share", r) == pytest.approx(75.0)
    r.log = SimpleNamespace(pools=[])
    assert run.read_metric("kv_pages_held_share", r) is None


def test_program_share_is_a_share_of_program_time():
    runs = [("jit_pf(1)", 0.5), ("jit_pf(2)", 0.25), ("jit_step(3)", 1.25)]
    trace = SimpleNamespace(module_times=lambda p: [
        t for n, t in runs if n.startswith(p)])
    r = SimpleNamespace(trace=trace)
    assert program_share(r, "jit_pf") == pytest.approx(37.5)
    assert program_share(r, "jit_other") is None


def _line(rate, first, second):
    return {"rate": rate, "wait_first_half_s": first,
            "wait_second_half_s": second}


def test_the_knee_is_the_last_rate_that_holds_on_every_seed():
    lines = [_line(1.0, 0.04, 0.05), _line(1.2, 0.04, 0.06),
             _line(1.4, 0.04, 0.07), _line(1.4, 0.05, 0.09),
             _line(1.6, 0.05, 0.30), _line(1.6, 0.05, 0.06)]
    assert calibrate.knee(lines) == pytest.approx(
        {"knee_per_s": 1.4, "rate_per_s": 1.12})
    assert calibrate.holds(0.01, 0.09) and not calibrate.holds(0.3, 0.55)
    assert calibrate.knee([_line(2.0, 0.1, 9.0)])["knee_per_s"] is None


def test_backlog_counts_a_request_never_admitted_until_the_last_tick():
    recs = [SimpleNamespace(due=1.0, admit_tick=1.1),
            SimpleNamespace(due=2.0, admit_tick=None)]
    r = SimpleNamespace(drained=5.0, due_in_window=lambda: recs)
    assert calibrate.backlog(r) == pytest.approx(
        {"wait_first_half_s": 0.1, "wait_second_half_s": 3.0})
