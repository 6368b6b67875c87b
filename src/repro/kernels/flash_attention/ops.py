"""Jit'd public wrapper for the flash attention kernels, differentiable.

``flash_attention`` runs the forward kernel and carries a ``custom_vjp``
whose backward runs the dK/dV and dQ kernels from the saved logsumexp, so
no (S, S) score tensor reaches HBM in either pass.  On TPU the Pallas
kernels run compiled; on the CPU ``interpret=True`` executes their bodies
in Python for validation against the jnp oracle in ``ref.py``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_backward, flash_forward


# the block length of every kernel: the fastest forward and the fastest
# backward of the sweep over q and k blocks of 128-1024 on a v5e chip at
# (B 2, S 2048, H 16, D 128) in bf16 (PERF.md §6)
PREFERRED_BLOCK = 1024
MIN_BLOCK = 128


def block_size(s: int) -> int | None:
    """The q and k block for sequence length ``s``: the largest of
    PREFERRED_BLOCK, half that, ... down to MIN_BLOCK that divides s;
    None where none does."""
    b = PREFERRED_BLOCK
    while b > MIN_BLOCK and s % b:
        b //= 2
    return None if s % b else b


class _Static(NamedTuple):
    causal: bool
    window: int | None
    block_q: int
    block_k: int
    interpret: bool


def _heads_first(x):
    return jnp.swapaxes(x, 1, 2)          # (B,S,H,D) <-> (B,H,S,D)


def _forward(q, k, v, st: _Static):
    qt, kt, vt = _heads_first(q), _heads_first(k), _heads_first(v)
    o, lse = flash_forward(qt, kt, vt, causal=st.causal, window=st.window,
                           block_q=st.block_q, block_k=st.block_k,
                           interpret=st.interpret)
    return _heads_first(o), (qt, kt, vt, o, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, st: _Static):
    return _forward(q, k, v, st)[0]


def _flash_bwd(st: _Static, res, do):
    qt, kt, vt, o, lse = res
    dq, dk, dv = flash_backward(
        qt, kt, vt, o, lse, _heads_first(do), causal=st.causal,
        window=st.window, block_q=st.block_q, block_k=st.block_k,
        interpret=st.interpret)
    return _heads_first(dq), _heads_first(dk), _heads_first(dv)


_flash.defvjp(_forward, _flash_bwd)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool = False) -> jax.Array:
    """q: (B,S,H,D); k/v: (B,S,K,D) -> (B,S,H,D).

    ``block_q``/``block_k`` (each at most S) default to ``block_size``."""
    s = q.shape[1]
    assert q.shape[2] % k.shape[2] == 0, (q.shape, k.shape)
    block_q = min(block_q or block_size(s) or s, s)
    block_k = min(block_k or block_size(s) or s, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    return _flash(q, k, v, _Static(causal, window, block_q, block_k,
                                   interpret))
