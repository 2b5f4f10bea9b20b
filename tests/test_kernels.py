"""Per-kernel validation: shape/dtype sweeps against the ref.py oracles,
all in Pallas interpret mode (the CPU contract for the TPU kernels)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.gemm_os import gemm_os, spatial_utilization

# interpret-mode model/kernel tests: minutes on a throttled CPU
pytestmark = pytest.mark.slow


def _rand(key, shape, dtype):
    if jnp.issubdtype(dtype, jnp.integer):
        return jax.random.randint(key, shape, -128, 128).astype(dtype)
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


@pytest.mark.parametrize("backend,interpret",
                         [("tpu", False), ("cpu", True), ("gpu", None)])
def test_ops_interpret_mode_follows_backend(monkeypatch, backend, interpret):
    # compiled on the TPU, interpreted on the CPU, refused anywhere else:
    # no silent interpret-mode fallback on an accelerator
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="gpu"):
            ops._interpret()
    else:
        assert ops._interpret() is interpret


# ---------------------------------------------------------------------------
# gemm_os
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
@pytest.mark.parametrize("mkn", [(8, 8, 8), (100, 300, 200), (128, 128, 128),
                                 (1, 512, 96), (257, 129, 65)])
def test_gemm_os_matches_ref(dtype, mkn):
    M, K, N = mkn
    x = _rand(jax.random.key(0), (M, K), dtype)
    w = _rand(jax.random.key(1), (K, N), dtype)
    got = gemm_os(x, w, block=(64, 64, 64), interpret=True)
    want = ref.gemm_ref(x, w)
    if dtype == jnp.int8:
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=2e-2 if dtype == jnp.bfloat16 else 2e-3,
            atol=2e-1 if dtype == jnp.bfloat16 else 2e-3)


@pytest.mark.parametrize("block", [(8, 8, 8), (32, 16, 64), (128, 128, 128)])
def test_gemm_os_block_sweep(block):
    x = _rand(jax.random.key(2), (96, 160), jnp.float32)
    w = _rand(jax.random.key(3), (160, 72), jnp.float32)
    got = gemm_os(x, w, block=block, interpret=True)
    np.testing.assert_allclose(got, ref.gemm_ref(x, w), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("scale", [1.0, 0.01, 0.0005])
def test_quant_epilogue_exact(scale):
    x = _rand(jax.random.key(4), (64, 256), jnp.int8)
    w = _rand(jax.random.key(5), (256, 96), jnp.int8)
    got = ops.quant_matmul(x, w, scale, block=(32, 32, 64))
    np.testing.assert_array_equal(got, ref.gemm_ref(x, w, quant_scale=scale))
    assert got.dtype == jnp.int8


def test_int8_accumulates_in_int32():
    # 512 * 127 * 127 overflows int16 by far; int32 must hold it exactly
    x = jnp.full((8, 512), 127, jnp.int8)
    w = jnp.full((512, 8), 127, jnp.int8)
    got = gemm_os(x, w, block=(8, 8, 128), interpret=True)
    assert int(got[0, 0]) == 512 * 127 * 127


def test_spatial_utilization_formula():
    assert spatial_utilization(128, 128, 128) == 1.0
    assert spatial_utilization(1, 128, 128) == pytest.approx(1 / 128)
    assert spatial_utilization(129, 128, 128) == pytest.approx(129 / 256)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [
    # (B, Sq, Sk, H, KV, D, bq, bk)
    (1, 64, 64, 4, 4, 32, 32, 32),      # MHA
    (2, 100, 100, 8, 2, 32, 32, 32),    # GQA, ragged seq
    (2, 37, 53, 6, 3, 16, 8, 16),       # cross-ish lengths
    (1, 1, 64, 8, 1, 32, 16, 16),       # decode: one q row
])
@pytest.mark.parametrize("causal", [True, False])
def test_mha_matches_ref(shape, causal):
    B, Sq, Sk, H, KV, D, bq, bk = shape
    if causal and Sq > Sk:
        pytest.skip("causal assumes Sq <= Sk alignment")
    q = _rand(jax.random.key(0), (B, Sq, H, D), jnp.float32)
    k = _rand(jax.random.key(1), (B, Sk, KV, D), jnp.float32)
    v = _rand(jax.random.key(2), (B, Sk, KV, D), jnp.float32)
    got = ops.attention(q, k, v, causal=causal, bq=bq, bk=bk)
    want = ref.mha_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, rtol=3e-3, atol=3e-3)


def test_mha_kv_valid():
    q = _rand(jax.random.key(0), (2, 16, 4, 16), jnp.float32)
    k = _rand(jax.random.key(1), (2, 64, 2, 16), jnp.float32)
    v = _rand(jax.random.key(2), (2, 64, 2, 16), jnp.float32)
    got = ops.attention(q, k, v, causal=False, kv_valid=33, bq=8, bk=16)
    want = ref.mha_ref(q, k, v, causal=False, kv_valid=33)
    np.testing.assert_allclose(got, want, rtol=3e-3, atol=3e-3)
    # and it must differ from attending to the whole cache
    full = ref.mha_ref(q, k, v, causal=False)
    assert not np.allclose(got, full, atol=1e-3)


def test_mha_bf16():
    q = _rand(jax.random.key(0), (1, 32, 4, 32), jnp.bfloat16)
    k = _rand(jax.random.key(1), (1, 32, 2, 32), jnp.bfloat16)
    v = _rand(jax.random.key(2), (1, 32, 2, 32), jnp.bfloat16)
    got = ops.attention(q, k, v, bq=16, bk=16)
    want = ref.mha_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# conv_im2col
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", [
    # (H, W, C, K, R, stride)
    (12, 12, 16, 24, 3, 1),
    (12, 12, 16, 24, 3, 2),
    (8, 8, 8, 32, 1, 1),
    (14, 14, 16, 8, 7, 2),
    (9, 9, 16, 8, 3, 2),        # odd spatial
])
def test_conv_im2col_matches_lax(spec):
    H, W, C, K, R, stride = spec
    x = _rand(jax.random.key(0), (2, H, W, C), jnp.float32)
    w = _rand(jax.random.key(1), (R, R, C, K), jnp.float32)
    got = ops.conv2d(x, w, stride=stride)
    want = ref.conv2d_ref(x, w, stride=stride)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# reshuffle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cb", [8, 32, 128])
def test_blocked_layout(cb):
    x = _rand(jax.random.key(0), (5, 6, 256), jnp.float32)
    np.testing.assert_array_equal(ops.blocked_layout(x, cb),
                                  ref.blocked_layout_ref(x, cb))


def test_blocked_layout_pads_channels():
    x = _rand(jax.random.key(0), (4, 4, 100), jnp.float32)
    out = ops.blocked_layout(x, 128)
    assert out.shape == (1, 4, 4, 128)
    np.testing.assert_array_equal(out[0, :, :, :100], x)
    np.testing.assert_array_equal(out[0, :, :, 100:], 0)


@pytest.mark.parametrize("mn", [(128, 128), (100, 70), (257, 33), (1, 129)])
def test_tiled_transpose(mn):
    x = _rand(jax.random.key(0), mn, jnp.float32)
    np.testing.assert_array_equal(ops.transpose(x), x.T)


def test_on_the_fly_kt_equals_transpose_pass():
    """Voltra claim: the streamer's on-the-fly K^T gives the same math as
    a dedicated transposer pass, with zero extra memory traffic. We verify
    the math side: attention(q, k) == q @ transpose(k) softmaxed."""
    q = _rand(jax.random.key(0), (1, 8, 2, 16), jnp.float32)
    k = _rand(jax.random.key(1), (1, 8, 2, 16), jnp.float32)
    v = _rand(jax.random.key(2), (1, 8, 2, 16), jnp.float32)
    fused = ops.attention(q, k, v, causal=False, bq=8, bk=8)
    # dedicated pass: transpose k with the reshuffler kernel, then score
    s = jnp.einsum("bqhd,bhds->bhqs", q.transpose(0, 1, 2, 3),
                   jnp.stack([jnp.stack([ops.transpose(k[b, :, h, :])
                                         for h in range(2)])
                              for b in range(1)])) * (16 ** -0.5)
    p = jax.nn.softmax(s, axis=-1)
    manual = jnp.einsum("bhqs,bshd->bqhd", p, v)
    np.testing.assert_allclose(fused, manual, rtol=3e-3, atol=3e-3)


# ---------------------------------------------------------------------------
# maxpool (Sec. II-E aux module)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", [
    # (H, W, C, window, stride)
    (8, 8, 16, 2, 2),
    (9, 9, 8, 3, 2),
    (12, 12, 32, 3, 3),
    (10, 10, 8, 5, 1),      # arbitrary window, stride 1
])
def test_maxpool_matches_reduce_window(spec):
    from repro.kernels.maxpool import maxpool2d, maxpool2d_ref
    H, W, C, win, stride = spec
    x = _rand(jax.random.key(0), (2, H, W, C), jnp.float32)
    got = maxpool2d(x, window=win, stride=stride)
    np.testing.assert_array_equal(got, maxpool2d_ref(x, window=win,
                                                     stride=stride))
