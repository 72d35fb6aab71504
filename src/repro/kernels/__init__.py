"""Pallas TPU kernels + the kernel-backed execution layer.

  icq_dequant.py  — tile dequantization over the transposed bit-plane
                    layout (select-tree codebook lookup, in-VMEM v2
                    gap-stream decode; `dequant_padded[_v2]` cores)
  icq_matmul.py   — fused dequantize+matmul (`matmul_padded[_v2]` cores)
  paged_attention.py — S=1 decode attention over the paged KV block
                    pool (in-kernel page-table walk, online softmax;
                    streams only live blocks through VMEM)
  kmeans_assign.py— weighted-Lloyd accumulation (calibration hot loop)
  ref.py          — pure-jnp oracles (ground truth in tests)
  ops.py          — runtime-format conversion + kernel calls on runtime
                    dicts (prepared into the kernel layout per call)
  backend.py      — prepared layouts + per-call dispatch (the path every
                    model matmul takes for ICQ weights)
  autotune.py     — block-size sweeps, JSON-cached winners
  platform.py     — TPU/CPU detection, interpret/backend defaults
"""
