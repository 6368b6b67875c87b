"""Decoder-only transformer LM (dense GQA + optional MoE + optional SWA).

Covers assigned archs: granite-3-8b, llama3-405b, codeqwen1.5-7b, olmo-1b
(non-parametric LN), mixtral-8x7b (MoE top-2 + SWA), llama4-scout (MoE
top-1).  Layers are scanned (stacked params, leading "layers" dim) so HLO
size is O(1) in depth; remat is applied per layer by the trainer.

Interfaces (shared by every family module):
  init(cfg, key) / abstract(cfg) / specs(cfg)
  forward(cfg, params, batch)            -> (logits, aux)
  abstract_cache(cfg, batch, max_len)    -> cache SDS tree
  prefill(cfg, params, tokens)           -> (logits_last, cache)
  decode_step(cfg, params, cache, token) -> (logits, cache)

KV cache layout: k/v (L, S_max, B, K, hd) + "len" scalar — one
dynamic_update_slice per decode step writes the (L,1,B,K,hd) row (minimal
HBM traffic; see DESIGN.md §6).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.common import ModelConfig, TreeBuilder
from repro.obs import tp_paths
from repro.sharding.collective_matmul import ag_matmul, matmul_rs


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _build(cfg: ModelConfig, key, abstract: bool):
    tb = TreeBuilder(cfg, key, abstract=abstract)
    d, hd = cfg.d_model, cfg.hd
    nl = cfg.n_layers
    tb.leaf("embed/table", (cfg.padded_vocab, d), ("vocab", "table_d"), scale=0.02)

    tb.leaf("layers/attn_norm", (nl, d), ("layers", None), init="zeros")
    tb.leaf("layers/mlp_norm", (nl, d), ("layers", None), init="zeros")
    tb.leaf("layers/wq", (nl, d, cfg.n_heads * hd),
            ("layers", "embed", "heads"))
    tb.leaf("layers/wk", (nl, d, cfg.n_kv_heads * hd),
            ("layers", "embed", "kv"))
    tb.leaf("layers/wv", (nl, d, cfg.n_kv_heads * hd),
            ("layers", "embed", "kv"))
    tb.leaf("layers/wo", (nl, cfg.n_heads * hd, d),
            ("layers", "heads", "embed"))
    if cfg.moe_experts:
        e = cfg.moe_experts
        tb.leaf("layers/router", (nl, d, e), ("layers", "embed", None))
        tb.leaf("layers/w_gate", (nl, e, d, cfg.d_ff),
                ("layers", "expert", "embed", "ff"))
        tb.leaf("layers/w_up", (nl, e, d, cfg.d_ff),
                ("layers", "expert", "embed", "ff"))
        tb.leaf("layers/w_down", (nl, e, cfg.d_ff, d),
                ("layers", "expert", "ff", "embed"))
    else:
        tb.leaf("layers/w_gate", (nl, d, cfg.d_ff), ("layers", "embed", "ff"))
        tb.leaf("layers/w_up", (nl, d, cfg.d_ff), ("layers", "embed", "ff"))
        tb.leaf("layers/w_down", (nl, cfg.d_ff, d), ("layers", "ff", "embed"))

    tb.leaf("final_norm", (d,), (None,), init="zeros")
    if not cfg.tie_embeddings:
        tb.leaf("unembed", (d, cfg.padded_vocab), ("embed", "vocab"), scale=0.02)
    return tb.build()


def init(cfg: ModelConfig, key) -> dict:
    return _build(cfg, key, abstract=False)[0]


def abstract(cfg: ModelConfig) -> dict:
    return _build(cfg, None, abstract=True)[0]


def specs(cfg: ModelConfig) -> dict:
    return _build(cfg, None, abstract=True)[1]


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _norm(cfg: ModelConfig, x, scale):
    if cfg.norm == "nonparam":
        return L.nonparam_layer_norm(x)
    if cfg.norm == "layernorm":
        return L.layer_norm(x, 1.0 + scale, None)
    return L.rms_norm(x, scale)


def _layer(cfg: ModelConfig, lp: dict, x: jax.Array,
           cos: jax.Array, sin: jax.Array, tp: tuple = ()
           ) -> tuple[jax.Array, tuple]:
    """One transformer block. x: (B,S,D). Returns (x', (k, v, aux)).

    ``tp``: the tensor-parallel axes where ``L.tp_boundary_path`` gives
    the collective path.  The block is then Megatron sequence-parallel
    over them: the residual stream, its norms and adds are sharded over
    the sequence, and the q/k/v, out and MLP projections are collective
    matmuls that gather the sequence into the tensor-parallel region and
    scatter the sums back, their transfers under their own dots.

    Sequence parallelism of the plan's own (cfg.seq_axes non-empty)
    follows the Megatron-SP handoff under GSPMD: the residual stream /
    layer boundary is SEQ-SHARDED (so scan carries stay small), each norm
    output is gathered into the seq-unsharded tensor-parallel region, and
    each block output is reduce-scattered back before the residual add.
    Pinning only the boundary (without explicit handoffs) makes the
    weight-grad contractions conflict on the model axis and XLA
    materializes full unsharded fp32 weight grads (found in the 405b
    dry-run)."""
    if tp:
        x = L.constrain_batch(x, cfg.batch_axes, tp)
    else:
        x = L.seq_boundary(x, cfg.batch_axes, cfg.seq_axes)
    dt = x.dtype
    b, s, d = x.shape
    hd = cfg.hd
    with jax.named_scope("attention"):
        h = _norm(cfg, x, lp["attn_norm"])
        if cfg.seq_axes:
            h = L.constrain_batch(h, cfg.batch_axes, ())   # gather into TP
        ws = (lp["wq"].astype(dt), lp["wk"].astype(dt), lp["wv"].astype(dt))
        q, k, v = (ag_matmul(h, ws, axes=tp) if tp else
                   [jnp.einsum("bsd,dh->bsh", h, w) for w in ws])
        q = q.reshape(b, s, cfg.n_heads, hd)
        k = k.reshape(b, s, cfg.n_kv_heads, hd)
        v = v.reshape(b, s, cfg.n_kv_heads, hd)
        q = L.apply_rope(q, cos, sin)
        k = L.apply_rope(k, cos, sin)
        o = L.attention(q, k, v, causal=True, window=cfg.window,
                        unroll=cfg.scan_unroll, batch_axes=cfg.batch_axes,
                        head_axes=cfg.head_axes)
        o = o.reshape(b, s, cfg.n_heads * hd)
        o = (matmul_rs(o, lp["wo"].astype(dt), axes=tp) if tp else
             jnp.einsum("bsh,hd->bsd", o, lp["wo"].astype(dt)))
        if cfg.seq_axes:
            o = L.seq_boundary(o, cfg.batch_axes, cfg.seq_axes)  # RS back
        x = x + o

    with jax.named_scope("mlp"):
        h2 = _norm(cfg, x, lp["mlp_norm"])
        if cfg.seq_axes:
            h2 = L.constrain_batch(h2, cfg.batch_axes, ())
        aux = jnp.zeros((), jnp.float32)
        if cfg.moe_experts:
            moe_out, aux = L.moe_block(
                lp, h2, n_experts=cfg.moe_experts, top_k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity_factor)
            if cfg.seq_axes:
                moe_out = L.seq_boundary(moe_out, cfg.batch_axes,
                                         cfg.seq_axes)
            x = x + moe_out
        else:
            m = (L.mlp_swiglu(lp, h2, tp) if cfg.act == "swiglu"
                 else L.mlp_gelu(lp, h2))
            if cfg.seq_axes:
                m = L.seq_boundary(m, cfg.batch_axes, cfg.seq_axes)
            x = x + m
    return x, (k, v, aux)


def forward(cfg: ModelConfig, params: dict, batch: dict,
            collect_cache: bool = False, last_only: bool = False):
    """batch: {"tokens": (B,S) int32}. Returns (logits, aux_loss[, kv]).

    ``last_only``: unembed only the final position (prefill path — avoids
    materializing (B,S,vocab) logits)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    dt = cfg.activation_dtype
    path = L.tp_boundary_path(cfg, s, jax.sharding.get_abstract_mesh().shape)
    tp_paths().counter(path).inc()
    tp = cfg.head_axes if path == "collective" else ()
    with jax.named_scope("embed"):
        x = params["embed"]["table"].astype(dt)[tokens]
        if tp:
            # the lookup's partial sums over the vocabulary's shards meet
            # here, scattered over the sequence as the first block takes
            # them; left unpinned, GSPMD reshards the tokens instead
            x = L.constrain_batch(x, cfg.batch_axes)
    pos = jnp.arange(s)
    cos, sin = L.rope_angles(pos, cfg.hd, cfg.rope_theta)

    def body(carry, lp):
        y, (k, v, aux) = _layer(cfg, lp, carry, cos, sin, tp)
        ys = (jnp.swapaxes(k, 0, 1), jnp.swapaxes(v, 0, 1), aux) \
            if collect_cache else (aux,)
        return y, ys

    x, ys = jax.lax.scan(L.maybe_remat(body, cfg.remat), x,
                         params["layers"], unroll=cfg.scan_unroll)
    aux = jnp.sum(ys[-1])
    with jax.named_scope("head"):
        x = _norm(cfg, x, params["final_norm"])
        if last_only:
            x = x[:, -1:]
        unemb = (params["embed"]["table"].astype(dt).T if cfg.tie_embeddings
                 else params["unembed"].astype(dt))
        logits = jnp.einsum("bsd,dv->bsv", x, unemb)
        if cfg.logit_softcap:
            logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    if collect_cache:
        return logits, aux, (ys[0], ys[1])   # (L,S,B,K,hd) each
    return logits, aux


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def abstract_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    dt = cfg.activation_dtype
    shape = (cfg.n_layers, max_len, batch, cfg.n_kv_heads, cfg.hd)
    return {
        "k": jax.ShapeDtypeStruct(shape, dt),
        "v": jax.ShapeDtypeStruct(shape, dt),
        "len": jax.ShapeDtypeStruct((), jnp.int32),
    }


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    dt = cfg.activation_dtype
    shape = (cfg.n_layers, max_len, batch, cfg.n_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt),
            "len": jnp.zeros((), jnp.int32)}


def cache_max_len(cfg: ModelConfig, seq_len: int) -> int:
    """SWA archs bound the live cache by the window size."""
    if cfg.window is not None:
        return min(seq_len, cfg.window)
    return seq_len


def prefill(cfg: ModelConfig, params: dict, tokens: jax.Array,
            max_len: int) -> tuple[jax.Array, dict]:
    """Run the full prompt; build the cache. Returns (last-token logits,
    cache).  If max_len < prompt length (SWA), keep the trailing window."""
    b, s = tokens.shape
    logits, _, (kc, vc) = forward(cfg, params, {"tokens": tokens},
                                  collect_cache=True, last_only=True)
    if max_len >= s:
        pad = max_len - s
        kc = jnp.pad(kc, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        vc = jnp.pad(vc, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    else:
        kc = kc[:, s - max_len:]
        vc = vc[:, s - max_len:]
    cache = {"k": kc, "v": vc,
             "len": jnp.asarray(min(s, max_len), jnp.int32)}
    return logits[:, -1], cache


def decode_step(cfg: ModelConfig, params: dict, cache: dict,
                token: jax.Array, pos: jax.Array
                ) -> tuple[jax.Array, dict]:
    """token: (B,) int32; pos: absolute position (for RoPE).  Writes the
    new kv at slot cache["len"] % max_len (ring buffer for SWA)."""
    b = token.shape[0]
    dt = cfg.activation_dtype
    max_len = cache["k"].shape[1]
    slot = cache["len"] % max_len
    x = params["embed"]["table"].astype(dt)[token][:, None]   # (B,1,D)
    cos, sin = L.rope_angles(jnp.asarray(pos).reshape(1), cfg.hd,
                             cfg.rope_theta)

    def body(carry, xs):
        x, = carry
        lp, kc, vc = xs
        h = _norm(cfg, x, lp["attn_norm"])
        q = jnp.einsum("bsd,dh->bsh", h, lp["wq"].astype(dt)
                       ).reshape(b, 1, cfg.n_heads, cfg.hd)
        k = jnp.einsum("bsd,dh->bsh", h, lp["wk"].astype(dt)
                       ).reshape(b, 1, cfg.n_kv_heads, cfg.hd)
        v = jnp.einsum("bsd,dh->bsh", h, lp["wv"].astype(dt)
                       ).reshape(b, 1, cfg.n_kv_heads, cfg.hd)
        q = L.apply_rope(q, cos[None], sin[None])
        k = L.apply_rope(k, cos[None], sin[None])
        # write new kv into this layer's slot
        kc = jax.lax.dynamic_update_slice(
            kc, jnp.swapaxes(k, 0, 1), (slot, 0, 0, 0))
        vc = jax.lax.dynamic_update_slice(
            vc, jnp.swapaxes(v, 0, 1), (slot, 0, 0, 0))
        n_valid = jnp.minimum(cache["len"] + 1, max_len)
        o = L.decode_attention(
            q, jnp.swapaxes(kc, 0, 1), jnp.swapaxes(vc, 0, 1), n_valid,
            window=None)   # ring buffer already bounds the window
        o = jnp.einsum("bsh,hd->bsd",
                       o.reshape(b, 1, cfg.n_heads * cfg.hd),
                       lp["wo"].astype(dt))
        x = x + o
        h2 = _norm(cfg, x, lp["mlp_norm"])
        if cfg.moe_experts:
            moe_out, _ = L.moe_block(
                lp, h2, n_experts=cfg.moe_experts, top_k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity_factor)
            x = x + moe_out
        else:
            x = x + (L.mlp_swiglu(lp, h2) if cfg.act == "swiglu"
                     else L.mlp_gelu(lp, h2))
        return (x,), (jnp.swapaxes(k, 0, 1)[0], jnp.swapaxes(v, 0, 1)[0])

    (x,), (k_new, v_new) = jax.lax.scan(
        body, (x,), (params["layers"], cache["k"], cache["v"]),
        unroll=cfg.scan_unroll)
    # single write of the (L,1,B,K,hd) row into the cache
    k_cache = jax.lax.dynamic_update_slice(
        cache["k"], k_new[:, None], (0, slot, 0, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(
        cache["v"], v_new[:, None], (0, slot, 0, 0, 0))
    x = _norm(cfg, x, params["final_norm"])
    unemb = (params["embed"]["table"].astype(dt).T if cfg.tie_embeddings
             else params["unembed"].astype(dt))
    logits = jnp.einsum("bsd,dv->bsv", x, unemb)[:, 0]
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    new_cache = {"k": k_cache, "v": v_cache, "len": cache["len"] + 1}
    return logits, new_cache
