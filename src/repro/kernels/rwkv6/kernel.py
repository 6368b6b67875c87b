"""WKV6 chunked Pallas-TPU kernel.

TPU adaptation (DESIGN.md §2): the CUDA RWKV kernels walk the sequence one
step per thread-block with the state in registers/shared memory.  On TPU
we use the chunked matmul factorization instead, so the MXU does the work
and the (D,D) fp32 state lives in VMEM scratch across the sequential
chunk grid dimension:

  scores[t,j] = sum_d r[t,d] k[j,d] e^{ct[t,d]-cum[j,d]}   (t > j)
  out = scores @ V + (r.k*u) * v + (r e^{ct}) @ S
  S   = diag(e^{cum_C}) S + (k e^{cum_C - cum})^T V

with cum = per-chunk cumulative log-decay, ct = cum - logw (cum through
t-1).  Every exponent is a DIFFERENCE <= 0, so the math is exact and
overflow-free even under RWKV6's strongest data-dependent decays
(validated against the exact per-step oracle down to w ~ 1e-4).

Grid: (B, H, n_chunks), chunks innermost/sequential.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# fp32 MXU passes throughout: the oracle tests pin the kernel to the exact
# per-step scan, which a bf16 pass would miss by far more than 1e-3
_EXACT = dict(precision=jax.lax.Precision.HIGHEST,
              preferred_element_type=jnp.float32)


# The chip's own exp and log are approximations (on a v5e, log is off by
# up to 1e-4 absolute on (0.5, 1) and exp by 6.5e-6 relative, in XLA and
# in Mosaic alike), and every decay below is an exp of a sum of logs: the
# kernel then missed the exact per-step scan by 4e-3 at rwkv6-1.6b's
# width.  Both are computed in software from f32 adds and multiplies,
# which the chip does exactly: a few ulp, here and on the CPU alike.
_LOG2E = 1.4426950408889634
_LN2_HI = 0.693145751953125          # few mantissa bits: n * _LN2_HI is exact
_LN2_LO = 1.428606765330187e-06
_SQRT_HALF = 0.7071067811865476
# Cephes logf minimax polynomial for log(1 + x), x in [sqrt(.5)-1, sqrt(2)-1]
_LOG_POLY = (7.0376836292e-2, -1.1514610310e-1, 1.1676998740e-1,
             -1.2420140846e-1, 1.4249322787e-1, -1.6668057665e-1,
             2.0000714765e-1, -2.4999993993e-1, 3.3333331174e-1)


def _exp(x):
    """e**x for f32 x <= 88; 0 below e**-87 (f32's normal range)."""
    n = jnp.maximum(jnp.round(x * _LOG2E), -126.0)
    f = (x - n * _LN2_HI) - n * _LN2_LO          # |f| <= ln(2) / 2
    p = 1.0 / 5040.0                             # Taylor to f**7: 5e-9
    for c in (1 / 720, 1 / 120, 1 / 24, 1 / 6, 0.5, 1.0, 1.0):
        p = p * f + c
    two_n = jax.lax.bitcast_convert_type(
        jnp.left_shift(n.astype(jnp.int32) + 127, 23), jnp.float32)
    return jnp.where(x < -87.0, 0.0, p * two_n)


def _log(w):
    """ln(w) for normal f32 w > 0 (Cephes logf)."""
    bits = jax.lax.bitcast_convert_type(w, jnp.int32)
    e = (jnp.right_shift(bits, 23) & 0xFF) - 126   # w = m * 2**e
    m = jax.lax.bitcast_convert_type((bits & 0x007FFFFF) | 0x3F000000,
                                     jnp.float32)  # m in [0.5, 1)
    low = m < _SQRT_HALF
    e = jnp.where(low, e - 1, e).astype(jnp.float32)
    x = jnp.where(low, m + m - 1.0, m - 1.0)
    z = x * x
    y = _LOG_POLY[0]
    for c in _LOG_POLY[1:]:
        y = y * x + c
    y = y * x * z + e * -2.12194440e-4 - 0.5 * z
    return x + y + e * 0.693359375


def _log_decay(w):
    return _log(jnp.clip(w.astype(jnp.float32),
                         jnp.finfo(jnp.float32).tiny, 1.0))


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, sout_ref, s_ref, *,
                 chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0, 0].astype(jnp.float32)          # (C,D)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)             # (1,D)

    logw = _log_decay(w_ref[0, 0])
    t_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive prefix sum over the chunk as a lower-triangular matmul
    # (Mosaic lowers no cumsum)
    tril = (t_i >= j_i).astype(jnp.float32)
    cum = jax.lax.dot(tril, logw, **_EXACT)      # (C,D)
    ct = cum - logw                              # decay start -> t-1

    r_in = r * _exp(ct)                       # ct <= 0: safe
    # intra-chunk pairwise decay: exponent ct[t]-cum[j] <= 0 for t > j,
    # so computing the DIFFERENCE first is overflow-free and exact (a
    # factorized r*e^{ct} @ (k*e^{-cum})^T matmul overflows fp32 under
    # strong decay; kept as the documented MXU-friendly variant for
    # bounded-decay deployments)
    dm = ct[:, None, :] - cum[None, :, :]        # (C,C,D)
    # the mask is built 3-D: Mosaic cannot reshape an i1 (C,C) to (C,C,1)
    d = r.shape[1]
    causal = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk, d), 0)
              > jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk, d), 1))
    att = jnp.where(causal, _exp(jnp.where(causal, dm, -jnp.inf)), 0.0)
    scores = jnp.sum(r[:, None, :] * k[None, :, :] * att, axis=-1)

    out = jax.lax.dot(scores, v, **_EXACT)
    diag = jnp.sum(r * k * u, axis=1)
    out = out + diag[:, None] * v
    out = out + jax.lax.dot(r_in, s_ref[...], **_EXACT)
    o_ref[0, 0] = out.astype(o_ref.dtype)

    cum_c = cum[chunk - 1:chunk, :]              # (1,D)
    k_out = k * _exp(cum_c - cum)             # exponent <= 0: safe
    s_ref[...] = _exp(cum_c).T * s_ref[...] + jax.lax.dot_general(
        k_out, v, (((0,), (0,)), ((), ())), **_EXACT)

    @pl.when(ci == nc - 1)
    def _flush():
        sout_ref[0, 0] = s_ref[...]


def wkv6_kernel(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
                u: jax.Array, *, chunk: int = 64,
                interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """r,k,v,w: (B,H,S,D); u: (H,D).  Returns (out, final_state)."""
    b, h, s, d = r.shape
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    kernel = functools.partial(_wkv6_kernel, chunk=chunk)
    out, state = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, d), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, chunk, d), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, chunk, d), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, chunk, d), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, d), lambda b_, h_, c_: (h_, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, chunk, d), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, d, d), lambda b_, h_, c_: (b_, h_, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, h, s, d), r.dtype),
            jax.ShapeDtypeStruct((b, h, d, d), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u.reshape(h, 1, d))
    return out, state
