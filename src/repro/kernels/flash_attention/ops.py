"""Jit'd public wrapper for the flash attention kernel.

On TPU the Pallas kernel runs compiled; on the CPU ``interpret=True``
executes the kernel body in Python for validation against the jnp oracle
in ``ref.py``.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention.kernel import flash_attention_kernel


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (B,S,H,D); k/v: (B,S,K,D) -> (B,S,H,D)."""
    return flash_attention_kernel(
        q, k, v, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret)
