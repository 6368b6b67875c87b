"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel ships as a trio (DESIGN.md S3):
  kernel.py  pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py     jit'd public wrapper (``interpret=True`` runs it on CPU)
  ref.py     pure-jnp oracle used by the allclose test sweeps

Kernels: flash_attention (GQA/causal/SWA, forward and backward through a
``custom_vjp``; the models' self-attention on TPU), rwkv6 (chunked WKV6),
rglru (chunked gated linear recurrence).
"""
