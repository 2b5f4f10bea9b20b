"""Sharding rules (divisibility fallbacks) + HLO cost-analysis parser."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.analysis import hlo_cost, roofline
from repro.launch.mesh import make_host_mesh
from repro.parallel.sharding import Rules


@pytest.fixture(scope="module")
def mesh():
    return make_host_mesh(model=1)   # (n_cpu, 1)


def test_spec_basics(mesh):
    r = Rules(mesh)
    assert r.spec((8, 16), "batch,seq") == P(("pod", "data")[1:][0] if False
                                             else "data")
    # replicated dims drop trailing Nones
    assert r.spec((8,), "") == P()


def test_divisibility_fallback(mesh):
    r = Rules(mesh)
    dp = mesh.shape["data"]
    if dp > 1:
        # a dim not divisible by the mesh axis falls back to replication
        assert r.spec((dp + 1, 4), "batch,") == P()
        assert r.spec((dp * 3, 4), "batch,") == P("data")
    else:
        pytest.skip("single-device mesh")


def test_axis_conflict_fallback(mesh):
    """Two logical dims mapping to the same mesh axis: second replicates."""
    r = Rules(mesh, {"batch": "data", "seq": "data"})
    dp = mesh.shape["data"]
    spec = r.spec((dp * 2, dp * 2), "batch,seq")
    assert spec == P("data")          # seq dropped (conflict)


def test_absent_axis_dropped():
    """'pod' axis is absent on the single-pod mesh: composite rules still
    work (this is what lets the same rules serve both meshes)."""
    m = make_host_mesh(model=1)
    r = Rules(m)
    spec = r.spec((8, 4), "batch,")
    assert spec in (P("data"), P())   # ("pod","data") -> ("data",)


def test_cons_is_identity_math(mesh):
    r = Rules(mesh)
    x = jnp.arange(16.0).reshape(8, 2)
    with jax.set_mesh(mesh):
        y = jax.jit(lambda a: r.cons(a, "batch,"))(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


# ---------------------------------------------------------------------------
# HLO cost parser (trip-count-aware)
# ---------------------------------------------------------------------------


def test_hlo_cost_counts_matmul_flops():
    M, K, N = 128, 256, 64

    def f(a, b):
        return a @ b

    lowered = jax.jit(f).lower(
        jax.ShapeDtypeStruct((M, K), jnp.float32),
        jax.ShapeDtypeStruct((K, N), jnp.float32))
    cost = hlo_cost.analyze_hlo(lowered.compile().as_text())
    want = 2 * M * K * N
    assert cost.flops == pytest.approx(want, rel=0.05)


def test_hlo_cost_multiplies_loop_trip_counts():
    """A scanned matmul must count L x the per-iteration FLOPs (this is
    the exact bug in XLA's own cost_analysis that hlo_cost fixes)."""
    L, M = 8, 64

    def f(x, ws):
        def body(h, w):
            return jnp.tanh(h @ w), None
        out, _ = jax.lax.scan(body, x, ws)
        return out

    lowered = jax.jit(f).lower(
        jax.ShapeDtypeStruct((M, M), jnp.float32),
        jax.ShapeDtypeStruct((L, M, M), jnp.float32))
    cost = hlo_cost.analyze_hlo(lowered.compile().as_text())
    want = L * 2 * M * M * M
    assert cost.flops == pytest.approx(want, rel=0.2)


def test_collective_bytes_parsed():
    mesh = make_host_mesh(model=1)
    if mesh.shape["data"] < 2:
        pytest.skip("need >1 device")
    r = Rules(mesh)
    n = mesh.shape["data"]

    def f(x):
        return x.sum(0)

    with jax.set_mesh(mesh):
        lowered = jax.jit(
            f, in_shardings=r.sharding((n * 4, 8), "batch,"),
            out_shardings=jax.sharding.NamedSharding(mesh, P())).lower(
            jax.ShapeDtypeStruct((n * 4, 8), jnp.float32))
        txt = lowered.compile().as_text()
    cost = hlo_cost.analyze_hlo(txt)
    assert cost.total_coll > 0          # an all-reduce must appear


def test_roofline_terms_positive_and_consistent():
    r = roofline.Roofline(flops=1e12, bytes_hbm=1e11, bytes_coll=5e9,
                          chips=256, coll_breakdown={}, model_flops=2.5e14)
    assert r.t_compute == pytest.approx(1e12 / roofline.PEAK_FLOPS)
    assert r.t_memory == pytest.approx(1e11 / roofline.HBM_BW)
    assert r.t_collective == pytest.approx(5e9 / roofline.ICI_BW)
    assert r.bottleneck == "memory"        # 0.122s > 0.1s > 0.005s
    assert 0 < r.roofline_frac <= 1.0 + 1e-9
    assert r.useful_flops_frac == pytest.approx(2.5e14 / (1e12 * 256))
