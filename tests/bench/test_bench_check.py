"""The benchmark's correctness check (bench/check.py) at smoke size on
the CPU: the served tokens of a sound run agree with the plain float32
reference, and the control (the reference in int8) does not."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_smoke  # noqa: E402


@pytest.fixture(scope="module")
def sound():
    return bench_smoke.run(control=True, metrics=[
        {"name": n, "unit": u} for n, u in
        (("kv_pages_held_share", "%"), ("ttft_p50_s", "s"),
         ("itl_p99_ms", "ms"))])


def test_sound_run_is_correct(sound):
    assert sound["correct"] is True
    assert sound["attempted"] == 8 and sound["failed"] == 0
    assert sound["numbers"]["tokens"] >= 40
    assert list(sound["checks"]) == list(bench_smoke.CONF["check"])


def test_sound_run_reads_its_metrics(sound):
    m = {k: v["value"] for k, v in sound["metrics"].items()}
    assert set(m) == {"kv_pages_held_share", "ttft_p50_s", "itl_p99_ms"}
    # the engine's own allocator: some of its 16 pages held, never more
    assert 0 < m["kv_pages_held_share"] <= 100
    assert m["ttft_p50_s"] > 0 and m["itl_p99_ms"] > 0


def test_control_reads_not_correct(sound):
    assert bench_smoke.verdict(bench_smoke.control_numbers(sound)) is False


def test_reference_matches_a_direct_forward():
    # the reference's logits at every position of a short sequence equal
    # a forward that computes each position with the whole sequence
    # visible only up to itself (causality by construction)
    import jax
    from bench import weights
    from bench.reference import dense_gqa
    w = dense_gqa.plain(weights.make(bench_smoke.CFG, 4))
    spec = dense_gqa.Spec.from_model(bench_smoke.CONF["model"])
    toks = np.arange(1, 25, dtype=np.int32) % 256
    full = np.asarray(dense_gqa.logits_at(spec, w, toks, np.arange(24)))
    for p in (0, 7, 23):
        part = dense_gqa.logits_at(spec, w, toks[:p + 1], np.array([p]))
        np.testing.assert_allclose(full[p], np.asarray(part)[0], rtol=1e-5,
                                   atol=1e-5)
    assert jax.numpy.isfinite(full).all()
