"""repro.kernels — Voltra's compute hot-spots as Pallas TPU kernels.

  gemm_os     — C1+C4: 3D-blocked output-stationary GeMM, fused INT8
                quant epilogue (pl.pallas_call + BlockSpec VMEM tiling)
  attention   — C3: fused flash-MHA, on-the-fly K^T, VMEM chain residency
  conv_im2col — 6-D AGU analogue: implicit-im2col Conv2D
  reshuffle   — data reshuffler: blocked layouts + tiled transpose
  maxpool     — Sec. II-E maxpool unit (arbitrary windows, lane-parallel)
  paged_attention — §III shared-memory streamers at serving time: flash-
                decode with the block-table gather inside the kernel
                (scalar-prefetched table, multi-page KV blocks)
  ops         — public jit'd wrappers (TPU: compiled; CPU: interpret)
  ref         — pure-jnp oracles (the correctness contract for tests)
"""
