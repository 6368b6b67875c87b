"""Jit'd public wrapper for the RG-LRU kernel (``interpret=True`` on CPU)."""

from __future__ import annotations

import functools

import jax

from repro.kernels.rglru.kernel import rglru_kernel


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rglru(a, x, *, chunk: int = 128, interpret: bool = False):
    """a, x: (B,S,R) -> (h (B,S,R), h_last (B,R))."""
    return rglru_kernel(a, x, chunk=chunk, interpret=interpret)
