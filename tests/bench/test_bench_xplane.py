"""The reduction from a profiler trace to the per-layer metrics
(bench/xplane.py), on a trace recorded on a TPU v5e: a quarter-second
traced window of the qwen2.5-3b.chat cell (one prefill, two decode
steps), committed gzipped."""
import gzip
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import xplane  # noqa: E402
from bench.readers import Run  # noqa: E402

DATA = Path(__file__).resolve().parent / "data" / "chat_tiny.xplane.pb.gz"


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "chat_tiny.xplane.pb"
    with gzip.open(DATA, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return xplane.read(str(path))


def test_window_and_busy_time(trace):
    assert trace.chips == 1
    assert trace.window_s == pytest.approx(0.235759213, abs=1e-6)
    assert trace.busy_s == pytest.approx(0.136583326, abs=1e-6)
    # idle time by host span adds up to the window less the busy time
    idle = sum(s for _, s in trace.idle_gaps())
    assert idle == pytest.approx(trace.window_s - trace.busy_s, abs=1e-6)
    assert {k for k, _ in trace.idle_gaps()} <= \
        set(xplane.HOST_SPANS) | {"none"}


def test_programs_and_kernel_found_by_name(trace):
    steps = trace.module_times("jit_step")
    assert len(steps) == 2
    assert sum(steps) / 2 == pytest.approx(0.039888894, abs=1e-6)
    assert trace.module_times("jit_pf")
    # the paged-attention kernel: one run per layer (36) per step
    assert sum(1 for n, _, _ in trace.ops if n == "_paged.9") == 72
    assert trace.top_ops()[0][0] == "_paged.9"
    assert not any(n.startswith("while") for n, _ in trace.top_ops())


def test_idle_share_reader(trace):
    import importlib.util
    sp = importlib.util.spec_from_file_location(
        "device_idle_share", ROOT / "bench" / "metrics" / "device_idle_share.py")
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    run = Run(0.0, 0.0, 1.0, 1.0, None, None, {}, trace=trace)
    assert mod.read(run) == pytest.approx(
        100 * (1 - 0.136583326 / 0.235759213), abs=1e-3)


def test_program_shares_of_program_time(trace):
    from bench.readers import program_share
    run = Run(0.0, 0.0, 1.0, 1.0, None, None, {}, trace=trace)
    decode = program_share(run, "jit_step")
    prefill = program_share(run, "jit_pf")
    every = sum(trace.module_times(""))
    assert every == pytest.approx(trace.busy_s, rel=0.01)
    assert decode == pytest.approx(100 * 2 * 0.039888894 / every, abs=1e-3)
    assert 0 < prefill and decode + prefill <= 100.0 + 1e-9
