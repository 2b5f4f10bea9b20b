"""Public jit'd wrappers for the kernel layer.

On the TPU backend these compile the Pallas kernels; on the CPU backend
they run them in interpret mode (bit-comparable semantics, the validation
mode this repo uses in its tests). Any other backend raises: a kernel
that silently interprets on an accelerator hides the device.
The model stack (repro.models) keeps pure-jnp paths so XLA SPMD handles
sharding; the kernels are the per-chip compute layer a TPU deployment
swaps in (see DESIGN.md "Kernel integration").
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro.kernels import attention as _attention
from repro.kernels import conv_im2col as _conv
from repro.kernels import gemm_os as _gemm
from repro.kernels import paged_attention as _paged
from repro.kernels import ref as _ref
from repro.kernels import reshuffle as _reshuffle


def _interpret() -> bool:
    """``interpret=`` for the default backend: compiled on ``tpu``,
    interpreted on ``cpu``, refused anywhere else."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default backend is {backend!r}")


def matmul(x: jax.Array, w: jax.Array, *,
           block: Tuple[int, int, int] = (128, 128, 128),
           out_dtype=None) -> jax.Array:
    """Output-stationary 3D-blocked matmul (Voltra C1)."""
    return _gemm.gemm_os(x, w, block=block, out_dtype=out_dtype,
                         interpret=_interpret())


def quant_matmul(x: jax.Array, w: jax.Array, scale: float, *,
                 block: Tuple[int, int, int] = (128, 128, 128)
                 ) -> jax.Array:
    """INT8 x INT8 -> INT32 accumulate -> fused quant epilogue -> INT8
    (Voltra C1 + C4)."""
    return _gemm.gemm_os(x, w, block=block, quant_scale=scale,
                         interpret=_interpret())


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, kv_valid: Optional[int] = None,
              bq: int = 128, bk: int = 128) -> jax.Array:
    """Fused flash-MHA with on-the-fly K^T (Voltra C3/PDMA analogue)."""
    return _attention.mha(q, k, v, causal=causal, kv_valid=kv_valid,
                          bq=bq, bk=bk, interpret=_interpret())


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    block_table: jax.Array, lengths, *,
                    kv_scale: Optional[float] = None,
                    window: int = 0) -> jax.Array:
    """Flash-decode over a paged KV pool: the block-table indirection runs
    INSIDE the kernel (scalar-prefetched table, double-buffered multi-page
    KV blocks, online softmax), so the per-layer dense gather of the PR-1 serving
    path never materializes (Voltra's shared-memory streamers; DESIGN.md
    "Paged attention"). q: (B, H, D) single-token decode, or (B, T, H, D)
    T-token query block (speculative verify — in-sweep causal masking,
    same kernel, same page traffic); pools: (P, page, KV, D); block_table:
    (B, n_blocks); lengths: (B,) live tokens INCLUDING the q block
    (base + T; T == 1 reduces to the old pos + 1 contract). window > 0 =
    sliding-window attention (hybrid local_attn layers): rows see at most
    the last `window` keys, and pages entirely below the window — which
    the serving engine recycles to scratch — are skipped in-grid."""
    return _paged.paged_attention(q, k_pool, v_pool, block_table, lengths,
                                  kv_scale=kv_scale, window=window,
                                  interpret=_interpret())


def conv2d(x: jax.Array, w: jax.Array, *, stride: int = 1) -> jax.Array:
    """Implicit-im2col Conv2D (6-D AGU analogue), SAME padding."""
    return _conv.conv2d(x, w, stride=stride, interpret=_interpret())


def blocked_layout(x: jax.Array, cb: int = 128) -> jax.Array:
    return _reshuffle.blocked_layout(x, cb, interpret=_interpret())


def transpose(x: jax.Array) -> jax.Array:
    return _reshuffle.tiled_transpose(x, interpret=_interpret())


# re-export oracles for tests/benchmarks
ref = _ref
