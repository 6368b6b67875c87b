"""Shared neural-net layers: norms, RoPE, attention flavors, MLPs, MoE,
gated linear recurrences (RG-LRU, RWKV6).

Everything is a pure function of (params subtree, activations).

Self-attention (``attention``) runs the Pallas flash kernel of
``repro.kernels.flash_attention`` (forward and backward, no (S, S) score
tensor in HBM) when the program is traced for a TPU, the call is prefill
or training (``q_offset`` 0, as many queries as keys), there is no logit
softcap and the kernel's blocks tile S.  On a mesh the kernel runs under
``jax.shard_map``, one call per shard, where the batch divides by its
shards and the q and kv heads by theirs.  Every other call keeps the jnp
math below, which GSPMD partitions: the CPU (tests, the dry-run), decode,
cross-attention, softcapped models and partitions that do not divide.
``attention_path`` makes that choice, and ``repro.obs.attention_paths()``
counts it at trace time.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.flash_attention.ops import block_size, flash_attention
from repro.obs import attention_paths


def maybe_remat(body, remat: str):
    """Wrap a scan body in jax.checkpoint per the config's remat mode."""
    if remat == "full":
        return jax.checkpoint(body)
    return body


def constrain_batch(x: jax.Array, batch_axes: tuple,
                    seq_axes: tuple = ()) -> jax.Array:
    """Pin the (batch[, seq]) dims' sharding on a (B,S,...) activation.
    No-op when batch_axes is empty (single-device tests).  Non-empty
    seq_axes = sequence parallelism at layer boundaries."""
    if not batch_axes and not seq_axes:
        return x
    from jax.sharding import PartitionSpec as P
    parts = [tuple(batch_axes) or None]
    if x.ndim >= 2:
        parts.append(tuple(seq_axes) or None)
    spec = P(*parts, *([None] * (x.ndim - len(parts))))
    return jax.lax.with_sharding_constraint(x, spec)


def seq_boundary(x: jax.Array, batch_axes: tuple, seq_axes: tuple
                 ) -> jax.Array:
    """Sequence-parallel boundary: constrain the PRIMAL to
    (batch, seq-sharded) but leave the COTANGENT unconstrained.

    with_sharding_constraint transposes to the same constraint on the
    cotangent; at Megatron-SP handoffs that forces seq-sharded weight-grad
    contractions that conflict with tensor-parallel sharding on the same
    mesh axis, and XLA materializes full unsharded fp32 weight grads
    (found in the 405b dry-run).  The asymmetric custom_vjp lets GSPMD
    pick the natural backward sharding."""
    if not batch_axes and not seq_axes:
        return x

    @jax.custom_vjp
    def ident(y):
        return constrain_batch(y, batch_axes, seq_axes)

    def fwd(y):
        return constrain_batch(y, batch_axes, seq_axes), None

    def bwd(_, g):
        return (g,)

    ident.defvjp(fwd, bwd)
    return ident(x)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array | None, eps: float = 1e-6
             ) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    if scale is not None:
        y = y * (1.0 + scale.astype(jnp.float32))
    return y.astype(dt)


def layer_norm(x: jax.Array, scale: jax.Array | None,
               bias: jax.Array | None, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    if scale is not None:
        y = y * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(dt)


def nonparam_layer_norm(x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """OLMo's non-parametric LayerNorm (no scale/bias)."""
    return layer_norm(x, None, None, eps)


def apply_norm(kind: str, x: jax.Array, p: dict | None) -> jax.Array:
    if kind == "rms":
        return rms_norm(x, p["scale"] if p else None)
    if kind == "layernorm":
        return layer_norm(x, p["scale"] if p else None,
                          p.get("bias") if p else None)
    if kind == "nonparam":
        return nonparam_layer_norm(x)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_angles(positions: jax.Array, head_dim: int, theta: float
                ) -> tuple[jax.Array, jax.Array]:
    """positions (..., S) -> cos/sin (..., S, head_dim//2), float32."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., S, H, D). cos/sin: (..., S, D/2) broadcast over heads."""
    dt = x.dtype
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c = cos[..., :, None, :]
    s = sin[..., :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c],
                           axis=-1).astype(dt)


# ---------------------------------------------------------------------------
# Attention (jnp reference math; GQA, causal, sliding window, cross)
# ---------------------------------------------------------------------------

def repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """(B,S,K,D) -> (B,S,K*n_rep,D)."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)
                            ).reshape(b, s, h * n_rep, d)


def _attention_dense(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     causal: bool, window: int | None,
                     q_offset, softcap: float) -> jax.Array:
    b, sq, h, d = q.shape
    _, sk, kh, _ = k.shape
    k = repeat_kv(k, h // kh)
    v = repeat_kv(v, h // kh)
    scale = 1.0 / math.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if softcap:
        logits = jnp.tanh(logits / softcap) * softcap
    qpos = q_offset + jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask = mask & (kpos <= qpos)
    if window is not None:
        mask = mask & (kpos > qpos - window)
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# materializing (B,H,Sq,Sk) above this many score elements per (B,H) pair
# is chunked over q blocks (flash-lite: bounds HBM transients the way the
# Pallas kernel bounds VMEM; the kernel remains the TPU hot path)
_CHUNK_THRESHOLD = 1 << 26
_Q_CHUNK = 1024


def _platform() -> str:
    """The platform a trace is lowered for: the default backend's."""
    return jax.default_backend()


def attention_path(q_shape: tuple, k_shape: tuple, *, q_offset=0,
                   softcap: float = 0.0, batch_axes: tuple = (),
                   head_axes: tuple = (), mesh_shape=None,
                   platform: str) -> str:
    """``"flash"`` where the Pallas kernel computes this self-attention,
    else ``"dense"`` (the jnp math, chunked over q for long sequences).

    ``batch_axes``/``head_axes`` are the mesh axes the batch and the heads
    are partitioned over, ``mesh_shape`` the size of each mesh axis.  The
    kernel then runs once per shard, so the batch must divide by the
    batch axes' size and both the q and the kv heads by the head axes':
    split contiguously by one factor, each shard's q heads keep their kv
    group."""
    sq = q_shape[1]
    if (platform != "tpu" or softcap
            or not isinstance(q_offset, int) or q_offset
            or sq != k_shape[1] or block_size(sq) is None):
        return "dense"
    sizes = mesh_shape or {}
    if any(a not in sizes for a in (*batch_axes, *head_axes)):
        return "dense"
    batch_shards = math.prod(sizes[a] for a in batch_axes)
    head_shards = math.prod(sizes[a] for a in head_axes)
    if (q_shape[0] % batch_shards or q_shape[2] % head_shards
            or k_shape[2] % head_shards):
        return "dense"
    return "flash"


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: int | None = None,
              q_offset: int = 0, softcap: float = 0.0,
              unroll: bool = False, batch_axes: tuple = (),
              head_axes: tuple = ()) -> jax.Array:
    """Self-attention. q: (B,Sq,H,D), k/v: (B,Sk,K,D) with H % K == 0.
    Returns (B,Sq,H,D).

    ``q_offset``: absolute position of q[0] relative to k[0] (prefill=0,
    decode=Sk-1).  ``window``: keys further than ``window`` behind the
    query are masked (sliding-window / local attention).
    ``batch_axes``/``head_axes``: the mesh axes the batch and the heads
    are partitioned over (the config's), on the mesh the trace runs
    under.  ``attention_path`` picks the flash kernel or the jnp math."""
    path = attention_path(q.shape, k.shape, q_offset=q_offset,
                          softcap=softcap, batch_axes=batch_axes,
                          head_axes=head_axes,
                          mesh_shape=jax.sharding.get_abstract_mesh().shape,
                          platform=_platform())
    attention_paths().counter(path).inc()
    if path == "flash":
        with jax.named_scope("flash"):
            return _flash_per_shard(q, k, v, causal=causal, window=window,
                                    batch_axes=batch_axes,
                                    head_axes=head_axes)
    return _attention_jnp(q, k, v, causal=causal, window=window,
                          q_offset=q_offset, softcap=softcap, unroll=unroll)


def _flash_per_shard(q, k, v, *, causal, window, batch_axes, head_axes):
    """The flash kernel over whole sequences, once per (batch, heads)
    shard of the context mesh; no collective runs inside the map, and
    autodiff transposes the map, so the backward kernels run per shard
    too.  Unpartitioned activations call the kernel directly."""
    kernel = partial(flash_attention, causal=causal, window=window)
    if not batch_axes and not head_axes:
        return kernel(q, k, v)
    spec = P(tuple(batch_axes) or None, None, tuple(head_axes) or None,
             None)
    # check_vma=False: the kernels' pallas_call outputs declare no
    # varying mesh axes, which the map's check requires of them
    return jax.shard_map(kernel, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def _attention_jnp(q, k, v, *, causal, window, q_offset, softcap, unroll):
    """Long sequences are processed in q-chunks so the score matrix
    transient stays bounded (each chunk still scores the full key range;
    the causal half-waste is what the flash kernel's block skipping
    removes on TPU)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if sq * sk < _CHUNK_THRESHOLD or sq <= _Q_CHUNK or sq % _Q_CHUNK:
        return _attention_dense(q, k, v, causal=causal, window=window,
                                q_offset=q_offset, softcap=softcap)
    nq = sq // _Q_CHUNK
    qc = jnp.moveaxis(q.reshape(b, nq, _Q_CHUNK, h, d), 1, 0)
    starts = jnp.arange(nq) * _Q_CHUNK

    def body(_, xs):
        qi, st = xs
        o = _attention_dense(qi, k, v, causal=causal, window=window,
                             q_offset=q_offset + st, softcap=softcap)
        return (), o

    _, outs = jax.lax.scan(body, (), (qc, starts), unroll=unroll)
    return jnp.moveaxis(outs, 0, 1).reshape(b, sq, h, d)


def cross_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    softcap: float = 0.0, unroll: bool = False) -> jax.Array:
    """Queries of one sequence over keys of another: always the jnp math."""
    attention_paths().counter("dense").inc()
    return _attention_jnp(q, k, v, causal=False, window=None, q_offset=0,
                          softcap=softcap, unroll=unroll)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len: jax.Array | int, *,
                     window: int | None = None) -> jax.Array:
    """Single-token decode: q (B,1,H,D), caches (B,S,K,D) with valid
    prefix ``cache_len``.  Position of q is cache_len-1 (the newest token
    is already written into the cache)."""
    b, s, kh, d = k_cache.shape
    h = q.shape[2]
    kq = repeat_kv(k_cache, h // kh)
    vq = repeat_kv(v_cache, h // kh)
    scale = 1.0 / math.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kq,
                        preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(s)[None, None, None, :]
    valid = kpos < jnp.asarray(cache_len).reshape(-1, 1, 1, 1)
    if window is not None:
        valid &= kpos >= jnp.asarray(cache_len).reshape(-1, 1, 1, 1) - window
    logits = jnp.where(valid, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, vq)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_swiglu(p: dict, x: jax.Array) -> jax.Array:
    dt = x.dtype
    gate = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(dt))
    up = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(dt))
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(dt) * up
    return jnp.einsum("bsf,fd->bsd", act, p["w_down"].astype(dt))


def mlp_gelu(p: dict, x: jax.Array) -> jax.Array:
    dt = x.dtype
    h = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(dt))
    if "b_up" in p:
        h = h + p["b_up"].astype(dt)
    h = jax.nn.gelu(h.astype(jnp.float32), approximate=True).astype(dt)
    out = jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(dt))
    if "b_down" in p:
        out = out + p["b_down"].astype(dt)
    return out


def apply_mlp(kind: str, p: dict, x: jax.Array) -> jax.Array:
    return mlp_swiglu(p, x) if kind == "swiglu" else mlp_gelu(p, x)


# ---------------------------------------------------------------------------
# MoE (GShard-style capacity dispatch; top-1 and top-2)
# ---------------------------------------------------------------------------

def moe_block(p: dict, x: jax.Array, *, n_experts: int, top_k: int,
              capacity_factor: float = 1.25) -> tuple[jax.Array, jax.Array]:
    """x: (B,S,D) -> (out, aux_loss).  Experts stacked on dim 0 of
    p['w_gate'|'w_up'|'w_down']: (E, D, F) / (E, F, D).

    GShard-style GROUPED dispatch: each batch row is a dispatch group
    with its own capacity (C = f*S*k/E), so the one-hot dispatch/combine
    tensors are (B, S, E, C) — LINEAR in tokens.  (An ungrouped
    (T, E, C_total) formulation is quadratic in T: ~43 TB for mixtral's
    train_4k cell.)  Dispatch/combine become all-to-alls when the expert
    dim is sharded (expert parallelism)."""
    b, s, d = x.shape
    dt = x.dtype
    # fixed-size dispatch groups (GShard): long sequences are split into
    # <=4096-token groups so the (groups, G, E, C) one-hot tensors stay
    # linear in tokens at any sequence length (32k prefill would
    # otherwise grow capacity with S)
    if s > 4096:
        assert s % 4096 == 0, s
        xg = x.reshape(b * (s // 4096), 4096, d)
        out, aux = moe_block(p, xg, n_experts=n_experts, top_k=top_k,
                             capacity_factor=capacity_factor)
        return out.reshape(b, s, d), aux
    # per-group capacity with a floor (min_capacity=4) so tiny decode
    # groups don't degenerate to cap=1
    capacity = max(4, -(-int(capacity_factor * s * top_k) // n_experts))
    capacity = min(capacity, s)

    router_logits = jnp.einsum(
        "bsd,de->bse", x.astype(jnp.float32),
        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(router_logits, axis=-1)

    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)          # (B,S,k)
    # aux load-balancing loss (Switch): E * sum_e f_e * p_e per group
    me = jnp.mean(probs, axis=1)                               # (B,E)
    ce = jnp.mean(jax.nn.one_hot(gate_idx[..., 0], n_experts,
                                 dtype=jnp.float32), axis=1)
    aux = n_experts * jnp.mean(jnp.sum(me * ce, axis=-1))

    combine = jnp.zeros((b, s, n_experts, capacity), jnp.float32)
    dispatch = jnp.zeros((b, s, n_experts, capacity), bool)
    occupancy = jnp.zeros((b, n_experts), jnp.int32)
    for slot in range(top_k):
        idx = gate_idx[..., slot]
        gv = gate_vals[..., slot]
        onehot = jax.nn.one_hot(idx, n_experts, dtype=jnp.int32)  # (B,S,E)
        # expert-buffer position within the group: running count in this
        # slot, offset by earlier slots' occupancy (GShard cumsum)
        pos = jnp.cumsum(onehot, axis=1) - 1 + occupancy[:, None, :]
        pos = jnp.where(onehot > 0, pos, -1)
        occupancy = occupancy + jnp.sum(onehot, axis=1)
        in_cap = (pos >= 0) & (pos < capacity)
        pos_c = jnp.clip(pos, 0, capacity - 1)
        oh_cap = jax.nn.one_hot(pos_c, capacity, dtype=jnp.float32) \
            * in_cap[..., None]
        combine = combine + oh_cap * gv[..., None, None]
        dispatch = dispatch | (oh_cap > 0)

    expert_in = jnp.einsum("bsec,bsd->ebcd", dispatch.astype(dt), x)
    gate = jnp.einsum("ebcd,edf->ebcf", expert_in, p["w_gate"].astype(dt))
    up = jnp.einsum("ebcd,edf->ebcf", expert_in, p["w_up"].astype(dt))
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(dt) * up
    expert_out = jnp.einsum("ebcf,efd->ebcd", act, p["w_down"].astype(dt))
    out = jnp.einsum("bsec,ebcd->bsd", combine.astype(dt), expert_out)
    return out, aux


# ---------------------------------------------------------------------------
# RG-LRU (RecurrentGemma) — jnp reference; Pallas kernel mirrors this
# ---------------------------------------------------------------------------

def rglru_scan(a: jax.Array, x: jax.Array, h0: jax.Array | None = None
               ) -> tuple[jax.Array, jax.Array]:
    """h_t = a_t * h_{t-1} + sqrt(1-a_t^2) * x_t  (elementwise, assoc-scan).

    a, x: (B, S, D) with a in (0,1).  Returns (h_all (B,S,D), h_last)."""
    a32 = a.astype(jnp.float32)
    x32 = x.astype(jnp.float32) * jnp.sqrt(
        jnp.maximum(1.0 - a32 * a32, 1e-12))
    if h0 is not None:
        # fold the carried state into step 0
        x32 = x32.at[:, 0].add(a32[:, 0] * h0.astype(jnp.float32))
        a32 = a32.at[:, 0].set(0.0 * a32[:, 0])

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, b1 * a2 + b2

    aa, hh = jax.lax.associative_scan(combine, (a32, x32), axis=1)
    return hh.astype(x.dtype), hh[:, -1]


def rglru_block(p: dict, x: jax.Array, h0: jax.Array | None = None,
                c: float = 8.0) -> tuple[jax.Array, jax.Array]:
    """Griffin's recurrent block core: input/rec gates + RG-LRU.

    x: (B,S,R).  p: log_a (R,), w_rx/w_ra gates (R,R)."""
    dt = x.dtype
    gate_x = jax.nn.sigmoid(
        jnp.einsum("bsr,rk->bsk", x, p["w_gx"].astype(dt))
        .astype(jnp.float32))
    gate_a = jax.nn.sigmoid(
        jnp.einsum("bsr,rk->bsk", x, p["w_ga"].astype(dt))
        .astype(jnp.float32))
    log_a = -c * gate_a * jax.nn.softplus(p["log_a"].astype(jnp.float32))
    a = jnp.exp(log_a).astype(x.dtype)
    gated_x = (x.astype(jnp.float32) * gate_x).astype(dt)
    h, h_last = rglru_scan(a, gated_x, h0)
    return h, h_last


def rglru_step(p: dict, x_t: jax.Array, h_prev: jax.Array, c: float = 8.0
               ) -> tuple[jax.Array, jax.Array]:
    """One decode step: x_t (B,R), h_prev (B,R) -> (out, h_new)."""
    dt = x_t.dtype
    gate_x = jax.nn.sigmoid(
        (x_t @ p["w_gx"].astype(dt)).astype(jnp.float32))
    gate_a = jax.nn.sigmoid(
        (x_t @ p["w_ga"].astype(dt)).astype(jnp.float32))
    log_a = -c * gate_a * jax.nn.softplus(p["log_a"].astype(jnp.float32))
    a = jnp.exp(log_a)
    xg = x_t.astype(jnp.float32) * gate_x
    h = a * h_prev.astype(jnp.float32) + jnp.sqrt(
        jnp.maximum(1.0 - a * a, 1e-12)) * xg
    return h.astype(dt), h.astype(jnp.float32)


# ---------------------------------------------------------------------------
# RWKV6 time-mix core (chunked linear attention with data-dependent decay)
# ---------------------------------------------------------------------------

def rwkv6_linear_attention(r: jax.Array, k: jax.Array, v: jax.Array,
                           w: jax.Array, u: jax.Array,
                           state0: jax.Array | None = None,
                           chunk: int = 64, unroll: bool = False
                           ) -> tuple[jax.Array, jax.Array]:
    """RWKV6 WKV recurrence, chunked form.

    r,k,v,w: (B, H, S, D); w = per-step decay in (0,1); u: (H, D) bonus.
    State S_t (B,H,D,D):  S_t = diag(w_t) S_{t-1} + k_t v_t^T
    out_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T)
    Returns (out (B,H,S,D), final state)."""
    b, h, s, d = r.shape
    assert s % chunk == 0, (s, chunk)
    n = s // chunk
    rf = r.astype(jnp.float32).reshape(b, h, n, chunk, d)
    kf = k.astype(jnp.float32).reshape(b, h, n, chunk, d)
    vf = v.astype(jnp.float32).reshape(b, h, n, chunk, d)
    wf = w.astype(jnp.float32).reshape(b, h, n, chunk, d)
    uf = u.astype(jnp.float32)

    logw = jnp.log(jnp.clip(wf, 1e-8, 1.0))
    cum = jnp.cumsum(logw, axis=3)                  # inclusive per-chunk
    w_in = jnp.exp(cum - logw)                      # decay from chunk start to t-1
    w_all = jnp.exp(cum[:, :, :, -1, :])            # (b,h,n,d) full-chunk decay
    w_out = jnp.exp(cum[:, :, :, -1:, :] - cum)     # decay from t to chunk end

    # --- intra-chunk: t attends to j<t with decay prod_{j<i<t} w_i,
    # plus the u-bonus on the diagonal (current token) -------------------
    ct = cum - logw                                 # cum up to t-1
    dmat = jnp.exp(ct[:, :, :, :, None, :] - cum[:, :, :, None, :, :])
    tt = jnp.arange(chunk)
    causal = (tt[:, None] > tt[None, :])[None, None, None, :, :, None]
    att = jnp.where(causal, dmat, 0.0)
    scores = jnp.einsum("bhntd,bhnjd,bhntjd->bhntj", rf, kf, att)
    intra_out = jnp.einsum("bhntj,bhnjd->bhntd", scores, vf)
    intra_out = intra_out + jnp.einsum(
        "bhntd,bhntv->bhntv", rf * kf * uf[None, :, None, None, :], vf)

    # --- inter-chunk: sequential scan over per-chunk states --------------
    k_scaled = kf * w_out                           # key decayed to chunk end
    s0 = (jnp.zeros((b, h, d, d), jnp.float32) if state0 is None
          else state0.astype(jnp.float32))
    kk = jnp.moveaxis(k_scaled, 2, 0)               # (n,b,h,chunk,d)
    vv = jnp.moveaxis(vf, 2, 0)
    wa = jnp.moveaxis(w_all, 2, 0)                  # (n,b,h,d)
    rr = jnp.moveaxis(rf, 2, 0)
    wi = jnp.moveaxis(w_in, 2, 0)

    def body(carry, xs):
        kc, vc, w_all_c, rc, w_in_c = xs            # (b,h,chunk,d)/(b,h,d)
        out_c = jnp.einsum("bhtd,bhdv->bhtv", rc * w_in_c, carry)
        new = carry * w_all_c[..., None] + jnp.einsum(
            "bhtd,bhtv->bhdv", kc, vc)
        return new, out_c

    final_state, inter_out = jax.lax.scan(body, s0, (kk, vv, wa, rr, wi),
                                          unroll=unroll)
    inter_out = jnp.moveaxis(inter_out, 0, 2)       # (b,h,n,chunk,d)

    out = (intra_out + inter_out).reshape(b, h, s, d)
    return out.astype(r.dtype), final_state


def rwkv6_step(r_t, k_t, v_t, w_t, u, state):
    """One decode step. r_t..w_t: (B,H,D); state (B,H,D,D) float32."""
    rf, kf, vf, wf = (x.astype(jnp.float32) for x in (r_t, k_t, v_t, w_t))
    uf = u.astype(jnp.float32)
    kv = jnp.einsum("bhd,bhv->bhdv", kf, vf)
    out = jnp.einsum("bhd,bhdv->bhv", rf, state + uf[None, :, :, None] * kv)
    new_state = state * wf[..., None] + kv
    return out.astype(r_t.dtype), new_state
