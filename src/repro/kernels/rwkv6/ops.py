"""Jit'd public wrapper for the WKV6 kernel (``interpret=True`` on CPU)."""

from __future__ import annotations

import functools

import jax

from repro.kernels.rwkv6.kernel import wkv6_kernel


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, w, u, *, chunk: int = 64, interpret: bool = False):
    """r,k,v,w: (B,H,S,D); u: (H,D) -> (out (B,H,S,D), state (B,H,D,D))."""
    return wkv6_kernel(r, k, v, w, u, chunk=chunk, interpret=interpret)
