"""Flash attention Pallas-TPU kernels: the forward pass with its per-row
logsumexp, and the two backward kernels (dK/dV and dQ).

TPU adaptation notes (DESIGN.md §2): FlashAttention's CUDA formulation
(shared-memory tiles + warp reductions) is re-tiled for the TPU memory
hierarchy — HBM->VMEM block copies driven by BlockSpec index maps, MXU-
aligned (128) q/k tiles, fp32 accumulators in VMEM scratch that persist
across the innermost (reduction) grid dimension.

Layout: q/o/dO (B,H,S,D), k/v (B,K,S,D), H a multiple of K (GQA folds a q
head onto its kv group in the index maps, without repeating k/v in HBM).
Row statistics (logsumexp, ``delta = rowsum(dO * O)``) are f32 and follow
the TPU convention of lane-broadcast rows: (B,H,S,128), every lane equal.

Precision: the MXU is fed the input dtype with f32 accumulation; scores
are scaled, masked and exponentiated in f32; P and dS are cast to the
input dtype for their dots (as the dense jnp path casts the probabilities
before P·V); every statistic and accumulator stays f32.

Masking: a block pair wholly above the causal diagonal or outside the
sliding window is skipped (``pl.when``), and its index maps clamp to the
last block that is needed, so a skipped step issues no new DMA.  Pairs
wholly inside the mask skip the elementwise masking too.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
# scoped VMEM the kernels may use: above the compiler's default so that
# 1024-wide blocks fit, well under a v5e core's 128 MiB
VMEM_LIMIT = 64 * 1024 * 1024

_NT = (((1,), (1,)), ((), ()))       # a @ b.T


def _k_range(qi, block_q, block_k, nk, causal, window):
    """First and last k block that q block ``qi`` attends to."""
    q_start = qi * block_q
    lo = 0
    if window is not None:
        lo = jnp.maximum(q_start - window + 1, 0) // block_k
    hi = (q_start + block_q - 1) // block_k if causal else nk - 1
    return lo, hi


def _q_range(ki, block_q, block_k, nq, causal, window):
    """First and last q block that attends to k block ``ki``."""
    k_start = ki * block_k
    lo = k_start // block_q if causal else 0
    hi = nq - 1
    if window is not None:
        hi = jnp.minimum((k_start + block_k + window - 2) // block_q, nq - 1)
    return lo, hi


def _clamp(i, lo_hi):
    lo, hi = lo_hi
    return jnp.minimum(jnp.maximum(i, lo), hi)


def _masked_steps(step, qi, ki, block_q, block_k, causal, window):
    """Run ``step(masked)`` for the pair (qi, ki) unless it is wholly
    masked; ``masked`` (static) says whether some of its entries are."""
    q_start, k_start = qi * block_q, ki * block_k
    q_end, k_end = q_start + block_q - 1, k_start + block_k - 1
    run, inside = True, True
    if causal:
        run = jnp.logical_and(run, k_start <= q_end)
        inside = jnp.logical_and(inside, k_end <= q_start)
    if window is not None:
        run = jnp.logical_and(run, k_end > q_start - window)
        inside = jnp.logical_and(inside, k_start > q_end - window)
    if run is True:          # no mask at all: one unmasked step
        step(False)
        return

    pl.when(jnp.logical_and(run, inside))(lambda: step(False))
    pl.when(jnp.logical_and(run, jnp.logical_not(inside)))(
        lambda: step(True))


def _mask(s, qi, ki, causal, window):
    bq, bk = s.shape
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    keep = True
    if causal:
        keep = kpos <= qpos
    if window is not None:
        keep = jnp.logical_and(keep, kpos > qpos - window)
    return jnp.where(keep, s, NEG_INF)


def _scores(q, k, qi, ki, masked, *, scale, causal, window):
    s = jax.lax.dot_general(q, k, _NT,
                            preferred_element_type=jnp.float32) * scale
    return _mask(s, qi, ki, causal, window) if masked else s


# every kernel's grid is (batch, heads, blocks, reduction blocks)
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=VMEM_LIMIT)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                scale, causal, window):
    block_q, block_k = q_ref.shape[2], k_ref.shape[2]
    qi, ki, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def step(masked):
        v = v_ref[0, 0]
        s = _scores(q_ref[0, 0], k_ref[0, 0], qi, ki, masked, scale=scale,
                    causal=causal, window=window)
        m_prev = m_sc[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # a row with nothing unmasked so far has m_new == NEG_INF and p == 1
        # here; its first unmasked key makes alpha 0 and wipes that out
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_sc[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[...] = alpha * acc_sc[...] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)
        l_sc[...] = jnp.broadcast_to(l_new, l_sc.shape)

    _masked_steps(step, qi, ki, block_q, block_k, causal, window)

    @pl.when(ki == nk - 1)
    def _flush():
        l = l_sc[:, :1]
        o_ref[0, 0] = (acc_sc[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_sc[...] + jnp.log(l_sc[...])


def flash_forward(q, k, v, *, causal: bool, window: int | None,
                  block_q: int, block_k: int, interpret: bool = False):
    """q (B,H,S,D), k/v (B,K,S,D) -> (o (B,H,S,D), lse (B,H,S,128) f32)."""
    b, h, s, d = q.shape
    group = h // k.shape[1]
    nq, nk = s // block_q, s // block_k
    kernel = functools.partial(_fwd_kernel, scale=1.0 / math.sqrt(d),
                               causal=causal, window=window)

    def kv_map(b_, h_, q_, k_):
        k_ = _clamp(k_, _k_range(q_, block_q, block_k, nk, causal, window))
        return b_, h_ // group, k_, 0

    def q_map(b_, h_, q_, k_):
        return b_, h_, q_, 0

    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[pl.BlockSpec((1, 1, block_q, d), q_map),
                  pl.BlockSpec((1, 1, block_k, d), kv_map),
                  pl.BlockSpec((1, 1, block_k, d), kv_map)],
        out_specs=[pl.BlockSpec((1, 1, block_q, d), q_map),
                   pl.BlockSpec((1, 1, block_q, LANES), q_map)],
        out_shape=[jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
                   jax.ShapeDtypeStruct((b, h, s, LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, LANES), jnp.float32),  # max
                        pltpu.VMEM((block_q, LANES), jnp.float32),  # sum
                        pltpu.VMEM((block_q, d), jnp.float32)],     # acc
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _probs_and_dscores(q, k, v, do, lse, delta, qi, ki, masked, *, scale,
                       causal, window):
    """P = exp(S - lse) and dS = P * (dO·Vᵀ - delta), both f32 (bq,bk)."""
    s = _scores(q, k, qi, ki, masked, scale=scale, causal=causal,
                window=window)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(do, v, _NT, preferred_element_type=jnp.float32)
    return p, p * (dp - delta)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_sc, dv_sc, *, scale, causal, window, nq):
    block_q, block_k = q_ref.shape[2], k_ref.shape[2]
    ki, j, nj = pl.program_id(2), pl.program_id(3), pl.num_programs(3)
    qi = j % nq          # j runs over (q head of the group, q block)

    @pl.when(j == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def step(masked):
        q, do = q_ref[0, 0], do_ref[0, 0]
        p, ds = _probs_and_dscores(
            q, k_ref[0, 0], v_ref[0, 0], do, lse_ref[0, 0][:, :1],
            delta_ref[0, 0][:, :1], qi, ki, masked, scale=scale,
            causal=causal, window=window)
        dv_sc[...] += jax.lax.dot(p.astype(do.dtype).T, do,
                                  preferred_element_type=jnp.float32)
        dk_sc[...] += jax.lax.dot(ds.astype(q.dtype).T, q,
                                  preferred_element_type=jnp.float32)

    _masked_steps(step, qi, ki, block_q, block_k, causal, window)

    @pl.when(j == nj - 1)
    def _flush():
        dk_ref[0, 0] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_sc, *, scale, causal, window):
    block_q, block_k = q_ref.shape[2], k_ref.shape[2]
    qi, ki, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    def step(masked):
        k = k_ref[0, 0]
        _, ds = _probs_and_dscores(
            q_ref[0, 0], k, v_ref[0, 0], do_ref[0, 0], lse_ref[0, 0][:, :1],
            delta_ref[0, 0][:, :1], qi, ki, masked, scale=scale,
            causal=causal, window=window)
        dq_sc[...] += jax.lax.dot(ds.astype(k.dtype), k,
                                  preferred_element_type=jnp.float32)

    _masked_steps(step, qi, ki, block_q, block_k, causal, window)

    @pl.when(ki == nk - 1)
    def _flush():
        dq_ref[0, 0] = (dq_sc[...] * scale).astype(dq_ref.dtype)


def flash_backward(q, k, v, o, lse, do, *, causal: bool,
                   window: int | None, block_q: int, block_k: int,
                   interpret: bool = False):
    """Gradients of ``flash_forward``'s o with respect to q, k and v, from
    its residuals (o, lse) and the output cotangent ``do`` (B,H,S,D)."""
    b, h, s, d = q.shape
    kh = k.shape[1]
    group = h // kh
    nq, nk = s // block_q, s // block_k
    scale = 1.0 / math.sqrt(d)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], lse.shape)

    # dK/dV: grid (B, K, k blocks, group * q blocks), q innermost
    def dkv_q_map(b_, g_, k_, j_):
        q_ = _clamp(j_ % nq, _q_range(k_, block_q, block_k, nq, causal,
                                      window))
        return b_, g_ * group + j_ // nq, q_, 0

    def dkv_kv_map(b_, g_, k_, j_):
        return b_, g_, k_, 0

    q_spec = pl.BlockSpec((1, 1, block_q, d), dkv_q_map)
    row_spec = pl.BlockSpec((1, 1, block_q, LANES), dkv_q_map)
    kv_spec = pl.BlockSpec((1, 1, block_k, d), dkv_kv_map)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          window=window, nq=nq),
        grid=(b, kh, nk, group * nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dQ: grid (B, H, q blocks, k blocks), k innermost
    def dq_q_map(b_, h_, q_, k_):
        return b_, h_, q_, 0

    def dq_kv_map(b_, h_, q_, k_):
        k_ = _clamp(k_, _k_range(q_, block_q, block_k, nk, causal, window))
        return b_, h_ // group, k_, 0

    q_spec = pl.BlockSpec((1, 1, block_q, d), dq_q_map)
    row_spec = pl.BlockSpec((1, 1, block_q, LANES), dq_q_map)
    kv_spec = pl.BlockSpec((1, 1, block_k, d), dq_kv_map)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          window=window),
        grid=(b, h, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv
