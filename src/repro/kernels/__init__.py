"""Pallas TPU kernels for the framework's compute hot-spots.

Layout per kernel (see EXAMPLE.md):
  <name>.py — pl.pallas_call + explicit BlockSpec VMEM tiling
  ops.py    — jit'd public wrappers with implementation={xla,pallas,ref}
  ref.py    — pure-jnp oracles used by the allclose test sweeps

Kernels: grouped_mlp (grouped-GEMM expert FFN over the MoE layer's sorted
ragged buffer — the hot-spot the paper sparsifies), flash_attention (32k prefill), decode_attention (paged
flash-decode over block-table KV pools — the repro/serve continuous-
batching hot path), rwkv6_kernel (WKV6 chunked scan for the assigned
SSM arch).
"""
