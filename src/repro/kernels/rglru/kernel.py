"""RG-LRU Pallas-TPU kernel: chunked gated linear recurrence.

TPU adaptation (DESIGN.md §2): a diagonal RNN has no matmul to feed the
MXU — the right TPU shape (as in the Griffin/recurrentgemma reference
kernels) is a sequential VPU scan over a VMEM-resident chunk: the chunk
of (a, x) is DMA'd HBM->VMEM once, the inner loop does one vector FMA per
step (h = a_t*h + sqrt(1-a_t^2)*x_t) writing rows back to the output
block, and the (1,R) carry persists in VMEM scratch across the sequential
chunk grid dimension.  Exact — no log-space clipping needed (a naive
telescoped-cumsum factorization overflows fp32 under Griffin's strong
decays; see ref.py oracle tests).

Grid: (B, n_chunks), chunks innermost/sequential per batch row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, x_ref, o_ref, hlast_ref, h_ref, *, chunk: int):
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def step(t, h):
        a_t = a_ref[0, pl.ds(t, 1), :].astype(jnp.float32)   # (1,R)
        x_t = x_ref[0, pl.ds(t, 1), :].astype(jnp.float32)
        h = a_t * h + jnp.sqrt(jnp.maximum(1.0 - a_t * a_t, 1e-12)) * x_t
        o_ref[0, pl.ds(t, 1), :] = h.astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_ref[...])
    h_ref[...] = h

    @pl.when(ci == nc - 1)
    def _flush():
        hlast_ref[0] = h.astype(hlast_ref.dtype)


def rglru_kernel(a: jax.Array, x: jax.Array, *, chunk: int = 128,
                 interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """a, x: (B,S,R). Returns (h (B,S,R), h_last (B,R) fp32)."""
    bsz, s, r = a.shape
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    kernel = functools.partial(_rglru_kernel, chunk=chunk)
    h, h_last = pl.pallas_call(
        kernel,
        grid=(bsz, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, r), lambda b_, c_: (b_, c_, 0)),
            pl.BlockSpec((1, chunk, r), lambda b_, c_: (b_, c_, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, chunk, r), lambda b_, c_: (b_, c_, 0)),
            pl.BlockSpec((1, 1, r), lambda b_, c_: (b_, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bsz, s, r), x.dtype),
            jax.ShapeDtypeStruct((bsz, 1, r), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((1, r), jnp.float32)],
        interpret=interpret,
    )(a, x)
    return h, h_last.reshape(bsz, r)
