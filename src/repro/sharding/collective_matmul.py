"""Collective matmuls at the tensor-parallel boundary of a block.

Megatron-style sequence parallelism keeps the residual stream between
blocks sharded over the sequence on the tensor-parallel (TP) axes, and
turns the block's TP matmuls into collective matmuls that overlap their
transfers with their own dots:

* ``ag_matmul``: the column-parallel matmul of a sequence-sharded input
  (``h @ [wq|wk|wv]``), fused with the all-gather over the sequence.
  Each device multiplies the chunk it holds while ``lax.ppermute`` brings
  the next one.
* ``matmul_rs``: the row-parallel matmul (``o @ wo``) fused with the
  reduce-scatter over the sequence.  Each device first computes the
  partial sum of the chunk its neighbour owns and sends it, then computes
  the next while that transfer runs, and adds what it receives.
* ``ag_matmul_rs``: both around a token-wise function (the MLP's
  ``h @ [w_gate|w_up]``, activation, ``@ w_down``), chunk by chunk, so
  the hidden activations are never put back into sequence order.

Each runs under ``jax.shard_map`` manual over the TP axes only: the other
mesh axes (the batch's, and the FSDP gather of the weights) stay with
GSPMD.  The chunk loop is unrolled in Python, so the scheduler sees every
dot and every permute.  The transpose of a gather is a scatter and
``ppermute`` transposes to ``ppermute``, so autodiff gives a backward that
overlaps the same way.  A forward output is the same sum of the same
per-device partial products as the all-reduce it replaces; a weight's
gradient adds its sequence chunks' partial products in float32 and rounds
once, as GSPMD's one dot over the whole sequence does.
"""

from __future__ import annotations

import math
from collections.abc import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _ring(n: int) -> list[tuple[int, int]]:
    """Each device sends to the one before it on the ring."""
    return [(i, (i - 1) % n) for i in range(n)]


def _size(axes: tuple) -> int:
    shape = jax.sharding.get_abstract_mesh().shape
    return math.prod(shape[a] for a in axes)


def _roll(y: jax.Array, shift: jax.Array, n: int) -> jax.Array:
    """``y`` rolled by ``shift`` of its ``n`` sequence chunks (axis 1),
    ``shift`` traced in [0, n): a select over the n static rolls, which
    fuses with its neighbours and transposes to the same form (a rolled
    slice of a doubled ``y``, as ``jnp.roll`` with a traced shift does,
    transposes to a doubled buffer)."""
    c = y.shape[1] // n
    return jax.lax.select_n(shift, *[jnp.roll(y, k * c, axis=1)
                                     for k in range(n)])


@jax.custom_vjp
def _chunk_dots(rows: tuple, w: jax.Array) -> tuple[jax.Array, ...]:
    """``r @ w`` for each sequence chunk ``r`` of ``rows``.  The weight's
    gradient sums the chunks' products in float32 and rounds once: left to
    autodiff it would add the chunks' rounded products in ``w``'s dtype."""
    return tuple(jnp.einsum("bsk,kn->bsn", r, w) for r in rows)


def _chunk_dots_fwd(rows, w):
    return _chunk_dots(rows, w), (rows, w)


def _chunk_dots_bwd(res, cts):
    rows, w = res
    d_rows = tuple(jnp.einsum("bsn,kn->bsk", ct, w) for ct in cts)
    dw = sum(jnp.einsum("bsk,bsn->kn", r, ct,
                        preferred_element_type=jnp.float32)
             for r, ct in zip(rows, cts))
    return d_rows, dw.astype(w.dtype)


_chunk_dots.defvjp(_chunk_dots_fwd, _chunk_dots_bwd)


def _gather_dots(x: jax.Array, ws: Sequence[jax.Array], axes: tuple,
                 n: int) -> list[tuple[jax.Array, ...]]:
    """Per shard: ``(chunk @ w for w in ws)`` for each of the n sequence
    chunks, in ring order (chunk (idx + hop) % n at ``hop``), each chunk
    in flight while the one before it is multiplied.

    The barrier on each dot's input keeps, in the backward, the sum of a
    chunk's cotangents out of its dot's output fusion, where it would wait
    for the cotangent the ring sends back."""
    rows = [x]
    for _ in range(n - 1):
        rows.append(jax.lax.ppermute(rows[-1], axes, _ring(n)))
    rows = tuple(jax.lax.optimization_barrier(r) for r in rows)
    return list(zip(*[_chunk_dots(rows, w) for w in ws]))


def _scatter_dots(rows: Sequence[jax.Array], w: jax.Array, axes: tuple,
                  n: int) -> jax.Array:
    """Per shard: this device's chunk of ``rows @ w`` summed over
    ``axes``, ``rows`` the n chunks in ring order.  A running sum travels
    the ring towards its chunk's owner, one device's partial product added
    per hop (at ``hop`` chunk (idx + 1 + hop) % n, after the last hop this
    device's own), each partial product computed while the sum before it
    is in flight.  The barrier keeps the add of the received sum out of the
    partial product's output fusion, where the dot would wait for it."""
    parts = _chunk_dots(tuple(rows[(1 + hop) % n] for hop in range(n)), w)
    acc = parts[0]
    for hop in range(1, n):
        acc = jax.lax.ppermute(acc, axes, _ring(n))
        acc = acc + jax.lax.optimization_barrier(parts[hop])
    return acc


def ag_matmul(x: jax.Array, ws: Sequence[jax.Array], *,
              axes: tuple) -> tuple[jax.Array, ...]:
    """``x @ w`` for each ``w`` of ``ws``, ``x`` gathered over the sequence.

    x: (B, S, D) with S sharded over ``axes``; each w: (D, N) with N
    sharded over ``axes``.  Returns one (B, S, N) per weight, S whole and
    N sharded over ``axes``.  Call under the mesh the arrays live on."""
    n = _size(axes)

    def body(x, *ws):
        idx = jax.lax.axis_index(axes)
        chunks = _gather_dots(x, ws, axes, n)
        return tuple(_roll(jnp.concatenate(out, axis=1), idx, n)
                     for out in zip(*chunks))

    return jax.shard_map(
        body, in_specs=(P(None, axes, None), *[P(None, axes)] * len(ws)),
        out_specs=tuple(P(None, None, axes) for _ in ws),
        axis_names=set(axes))(x, *ws)


def matmul_rs(y: jax.Array, w: jax.Array, *, axes: tuple) -> jax.Array:
    """``y @ w`` summed over ``axes`` and scattered over the sequence.

    y: (B, S, K) with K sharded over ``axes``; w: (K, D) with K sharded
    over ``axes``.  Returns (B, S, D) with S sharded over ``axes``.  Call
    under the mesh the arrays live on."""
    n = _size(axes)

    def body(y, w):
        idx = jax.lax.axis_index(axes)
        c = y.shape[1] // n
        ring = _roll(y, (n - idx) % n, n)
        return _scatter_dots([ring[:, h * c:(h + 1) * c] for h in range(n)],
                             w, axes, n)

    return jax.shard_map(
        body, in_specs=(P(None, None, axes), P(axes, None)),
        out_specs=P(None, axes, None), axis_names=set(axes))(y, w)


def ag_matmul_rs(x: jax.Array, ws: Sequence[jax.Array], w_out: jax.Array,
                 fn: Callable[..., jax.Array], *, axes: tuple) -> jax.Array:
    """``fn(*(x @ w for w in ws)) @ w_out``, ``fn`` token-wise: the
    sequence gathered into the first matmuls and the sum of the last
    scattered over it, one chunk at a time.

    x: (B, S, D) with S sharded over ``axes``; each w: (D, F) and w_out
    (F, D) with F sharded over ``axes``.  Returns (B, S, D) with S sharded
    over ``axes``.  Call under the mesh the arrays live on."""
    n = _size(axes)

    def body(x, w_out, *ws):
        rows = [fn(*outs) for outs in _gather_dots(x, ws, axes, n)]
        return _scatter_dots(rows, w_out, axes, n)

    seq = P(None, axes, None)
    return jax.shard_map(
        body, in_specs=(seq, P(axes, None), *[P(None, axes)] * len(ws)),
        out_specs=seq, axis_names=set(axes))(x, w_out, *ws)
