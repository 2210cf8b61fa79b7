"""Pallas TPU kernels for the compute hot-spots of the cloud's workloads.

Each kernel ``<name>.py`` contains a ``pl.pallas_call`` + explicit BlockSpec
VMEM tiling; ``ops.py`` exposes jit'd wrappers that dispatch between the
Pallas kernel (TPU / interpret mode) and the pure-jnp oracle in ``ref.py``.

Kernels:
- ``flash_attention``  — tiled online-softmax causal GQA attention (prefill).
- ``decode_attention`` — flash-decode: 1 query token vs a long KV cache.
- ``paged_decode_attention`` — flash-decode over a paged KV cache: each
  lane walks multi-page blocks up to its length, copying pages by the
  scalar-prefetched page table (serving's paged cache).
- ``selective_scan``   — Mamba1 selective SSM scan (chunked recurrence).
- ``ssd``              — Mamba2 state-space duality (chunked matmul form).
- ``rmsnorm``          — fused RMSNorm.
"""
