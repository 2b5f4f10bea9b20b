"""Operation and byte counts (``counts`` of bench/reference/dense_gqa.py)
and the readers that turn them into the roofline share and MFU, against
hand counts on a toy shape and a made-up trace, and against the counts of
both configurations as they were before the counts moved into the
reference module."""
import json
import importlib.util
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import xplane  # noqa: E402
from bench.readers import Run  # noqa: E402
from bench.reference import dense_gqa  # noqa: E402

S = dense_gqa.Spec(layers=2, d=8, heads=2, kv_heads=1, head_dim=4, d_ff=16,
                   vocab=10, rope_theta=1e4, eps=1e-6, tied=True)
PEAKS = {"bf16_flops_per_s": 1e4, "hbm_bytes_per_s": 100.0}
# contexts, and for each configuration the counts its cells were read
# with before the counts moved into the reference module: decode_flops,
# prefill_flops of each context's length, paged_attn_bytes and
# paged_attn_flops of all the contexts
CONTEXTS = [1, 700, 2048, 4096]
GOLDEN = {
    "qwen2.5-3b": (26704248832.0,
                   [6171688960.0, 3957323923456.0, 11983883075584.0,
                    25204094402560.0],
                   253513728.0, 2018672640.0),
    "granite-3-2b": (22509895680.0,
                     [5067059200.0, 3486372474880.0, 10652055777280.0,
                      22678299750400.0],
                     562053120.0, 2242969600.0),
}


def reader(name):
    sp = importlib.util.spec_from_file_location(
        name, ROOT / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod.read


def test_hand_counts():
    # q,k,v: 8 * (2 + 2) * 4; o: 2 * 4 * 8; SwiGLU: 3 * 8 * 16
    assert S.layer_matmul_params == 128 + 64 + 384
    # k and v, 2 layers, 1 head of 4, bf16
    assert S.kv_bytes_per_token == 32
    # 2 * 2 layers * 576 + 4 * 2 layers * 2 heads * 4 * 5 keys + head 2*8*10
    assert S.token_flops(5, True) == 2304 + 320 + 160
    assert S.token_flops(5, False) == 2304 + 320
    # 4 prompt tokens: matmuls, causal 1+2+3+4 keys, head at the last only
    assert S.prefill_flops(4) == 9216 + 64 * 10 + 160
    # 8 live tokens of KV, plus q in and out per request per layer
    assert S.paged_attn_bytes([5, 3]) == 8 * 32 + 2 * 2 * 32
    assert S.paged_attn_flops([5, 3]) == 4 * 2 * 2 * 4 * 8


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_configuration_counts_are_unchanged_by_the_move(name):
    from bench import run
    conf = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    c = run.load_reference(conf).counts(conf["model"])
    decode, prefill, attn_bytes, attn_flops = GOLDEN[name]
    assert c.decode_flops(CONTEXTS) == decode
    assert [c.prefill_flops(n) for n in CONTEXTS] == prefill
    assert c.paged_attn_bytes(CONTEXTS) == attn_bytes
    assert c.paged_attn_flops(CONTEXTS) == attn_flops


def _run(kernel_s=7.68, busy_s=10.0):
    ops = [("_paged.1", 100.0, 100.0 + kernel_s / 2),
           ("fusion.3", 104.0, 105.0),
           ("_paged.1", 106.0, 106.0 + kernel_s / 2)]
    tr = xplane.Trace(100.0, 120.0, 1, modules=[("jit_step(7)", 100.0, 106.0)],
                      ops=ops, spans=[], busy_s=busy_s, _first_chip=ops)
    log = type("Log", (), {})()
    log.steps = [(0.5, 1.0, [5, 3]), (5.0, 6.0, [9])]    # 2nd outside
    log.prefills = [(0.2, 0.4, 4)]
    log.recs = {}
    return Run(setup_s=1.0, t0=0.0, end=3.0, drained=3.0, log=log, counts=S,
               peaks=PEAKS, trace=tr, traced=(0.0, 2.0))


def test_roofline_share_against_hand_count():
    # 384 bytes at 100 B/s (3.84 s) bounds over 512 flops at 1e4/s
    assert reader("paged_attention_roofline")(_run()) == pytest.approx(50.0)


def test_mfu_against_hand_count():
    # decode: token_flops(5) + token_flops(3); prefill of 4 tokens
    f = 2784 + (2304 + 192 + 160) + 10016
    assert reader("mfu")(_run()) == pytest.approx(100 * f / (10.0 * 1e4))


def test_readers_find_nothing_without_a_trace():
    run = _run()
    run.trace = None
    for name in ("paged_attention_roofline", "mfu", "decode_step_ms",
                 "device_idle_share", "prefill_ms_per_ktok"):
        assert reader(name)(run) is None


def test_trace_reduction_on_made_up_events():
    ops = [("a", 0.0, 1.0), ("b", 0.5, 2.0), ("a", 3.0, 4.0)]
    spans = [("tick", 0.0, 5.0), ("wait", 2.5, 3.5)]
    tr = xplane.Trace(0.0, 5.0, 1, modules=[], ops=ops, spans=spans,
                      busy_s=3.0, _first_chip=ops)
    assert tr.top_ops() == [["a", 2.0], ["b", 1.5]]
    # idle: [2, 3) mid 2.5 -> wait (innermost), [4, 5) mid 4.5 -> tick
    assert tr.idle_gaps() == [["wait", 1.0], ["tick", 1.0]]
    assert reader("device_idle_share")(
        Run(0, 0, 5, 5, None, S, PEAKS, trace=tr)) == pytest.approx(40.0)
