"""RecurrentGemma / Griffin (arXiv:2402.19427) — RG-LRU + local-attention
hybrid, pattern 1 local-attn per 2 recurrent blocks.

Block kinds:
* recurrent: x -> {Wx -> conv1d(4) -> RG-LRU} ⊙ gelu(Wy) -> Wo
* local attention: MQA (kv=1) with sliding window + RoPE
Every block is followed by a GeGLU MLP; RMSNorm pre-norms throughout.

26 layers = 8 super-blocks of (rglru, rglru, attn) + 2 tail rglru blocks;
both groups are scanned (stacked params).  Serving state: per recurrent
block a (B,R) RG-LRU hidden + (B,3,R) conv tail; per attn block a
window-sized ring-buffer KV cache — O(window) memory, so this arch runs
``long_500k``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.common import ModelConfig, TreeBuilder

CONV_W = 4


def _rec_leaves(tb: TreeBuilder, prefix: str, n: int, cfg: ModelConfig):
    d = cfg.d_model
    r = cfg.rglru_dim or cfg.d_model
    tb.leaf(f"{prefix}/norm", (n, d), ("layers", None), init="zeros")
    tb.leaf(f"{prefix}/wx", (n, d, r), ("layers", "embed", "ff"))
    tb.leaf(f"{prefix}/wy", (n, d, r), ("layers", "embed", "ff"))
    tb.leaf(f"{prefix}/conv_w", (n, CONV_W, r), ("layers", "conv", "ff"))
    tb.leaf(f"{prefix}/conv_b", (n, r), ("layers", "ff"), init="zeros")
    tb.leaf(f"{prefix}/log_a", (n, r), ("layers", "ff"), init="zeros")
    tb.leaf(f"{prefix}/w_gx", (n, r, r), ("layers", "ff", "ff"))
    tb.leaf(f"{prefix}/w_ga", (n, r, r), ("layers", "ff", "ff"))
    tb.leaf(f"{prefix}/wo", (n, r, d), ("layers", "ff", "embed"))
    _mlp_leaves(tb, prefix, n, cfg)


def _attn_leaves(tb: TreeBuilder, prefix: str, n: int, cfg: ModelConfig):
    d, hd = cfg.d_model, cfg.hd
    tb.leaf(f"{prefix}/norm", (n, d), ("layers", None), init="zeros")
    tb.leaf(f"{prefix}/wq", (n, d, cfg.n_heads * hd), ("layers", "embed", "heads"))
    tb.leaf(f"{prefix}/wk", (n, d, cfg.n_kv_heads * hd), ("layers", "embed", "kv"))
    tb.leaf(f"{prefix}/wv", (n, d, cfg.n_kv_heads * hd), ("layers", "embed", "kv"))
    tb.leaf(f"{prefix}/wo", (n, cfg.n_heads * hd, d), ("layers", "heads", "embed"))
    _mlp_leaves(tb, prefix, n, cfg)


def _mlp_leaves(tb: TreeBuilder, prefix: str, n: int, cfg: ModelConfig):
    d = cfg.d_model
    tb.leaf(f"{prefix}/mlp_norm", (n, d), ("layers", None), init="zeros")
    tb.leaf(f"{prefix}/w_gate", (n, d, cfg.d_ff), ("layers", "embed", "ff"))
    tb.leaf(f"{prefix}/w_up", (n, d, cfg.d_ff), ("layers", "embed", "ff"))
    tb.leaf(f"{prefix}/w_down", (n, cfg.d_ff, d), ("layers", "ff", "embed"))


def n_supers(cfg: ModelConfig) -> tuple[int, int]:
    per = len(cfg.block_pattern)        # 3
    return cfg.n_layers // per, cfg.n_layers % per


def _build(cfg: ModelConfig, key, abstract: bool):
    tb = TreeBuilder(cfg, key, abstract=abstract)
    ns, tail = n_supers(cfg)
    tb.leaf("embed/table", (cfg.padded_vocab, cfg.d_model), ("vocab", "table_d"),
            scale=0.02)
    _rec_leaves(tb, "supers/rec1", ns, cfg)
    _rec_leaves(tb, "supers/rec2", ns, cfg)
    _attn_leaves(tb, "supers/attn", ns, cfg)
    if tail:
        _rec_leaves(tb, "tail", tail, cfg)
    tb.leaf("final_norm", (cfg.d_model,), (None,), init="zeros")
    if not cfg.tie_embeddings:
        tb.leaf("unembed", (cfg.d_model, cfg.padded_vocab), ("embed", "vocab"),
                scale=0.02)
    return tb.build()


def init(cfg, key):
    return _build(cfg, key, abstract=False)[0]


def abstract(cfg):
    return _build(cfg, None, abstract=True)[0]


def specs(cfg):
    return _build(cfg, None, abstract=True)[1]


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------

def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 tail: jax.Array | None) -> tuple[jax.Array, jax.Array]:
    """Depthwise causal conv1d, width CONV_W. x (B,S,R), w (CONV_W,R).
    ``tail``: (B,CONV_W-1,R) carried history. Returns (y, new_tail)."""
    if tail is None:
        tail = jnp.zeros((x.shape[0], CONV_W - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([tail, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i].astype(x.dtype)
            for i in range(CONV_W))
    return y + b.astype(x.dtype), xp[:, -(CONV_W - 1):]


def _rec_block(cfg, lp, x, h0=None, conv_tail=None):
    x = L.constrain_batch(x, cfg.batch_axes, cfg.seq_axes)
    dt = x.dtype
    h = L.rms_norm(x, lp["norm"])
    gate = jax.nn.gelu(jnp.einsum(
        "bsd,dr->bsr", h, lp["wy"].astype(dt)).astype(jnp.float32),
        approximate=True).astype(dt)
    u = jnp.einsum("bsd,dr->bsr", h, lp["wx"].astype(dt))
    u, new_tail = _causal_conv(u, lp["conv_w"], lp["conv_b"], conv_tail)
    rec, h_last = L.rglru_block(
        {"log_a": lp["log_a"], "w_gx": lp["w_gx"], "w_ga": lp["w_ga"]},
        u, h0)
    out = jnp.einsum("bsr,rd->bsd", rec * gate, lp["wo"].astype(dt))
    x = x + out
    h2 = L.rms_norm(x, lp["mlp_norm"])
    x = x + _geglu(lp, h2)
    return x, (h_last, new_tail)


def _geglu(lp, x):
    dt = x.dtype
    g = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, lp["w_gate"].astype(dt))
                    .astype(jnp.float32), approximate=True).astype(dt)
    up = jnp.einsum("bsd,df->bsf", x, lp["w_up"].astype(dt))
    return jnp.einsum("bsf,fd->bsd", g * up, lp["w_down"].astype(dt))


def _attn_block(cfg, lp, x, cos, sin):
    x = L.constrain_batch(x, cfg.batch_axes, cfg.seq_axes)
    dt = x.dtype
    b, s, d = x.shape
    hd = cfg.hd
    h = L.rms_norm(x, lp["norm"])
    q = jnp.einsum("bsd,dh->bsh", h, lp["wq"].astype(dt)
                   ).reshape(b, s, cfg.n_heads, hd)
    k = jnp.einsum("bsd,dh->bsh", h, lp["wk"].astype(dt)
                   ).reshape(b, s, cfg.n_kv_heads, hd)
    v = jnp.einsum("bsd,dh->bsh", h, lp["wv"].astype(dt)
                   ).reshape(b, s, cfg.n_kv_heads, hd)
    q = L.apply_rope(q, cos, sin)
    k = L.apply_rope(k, cos, sin)
    o = L.attention(q, k, v, causal=True, window=cfg.window,
                    unroll=cfg.scan_unroll, batch_axes=cfg.batch_axes,
                    head_axes=cfg.head_axes)
    o = jnp.einsum("bsh,hd->bsd", o.reshape(b, s, cfg.n_heads * hd),
                   lp["wo"].astype(dt))
    x = x + o
    h2 = L.rms_norm(x, lp["mlp_norm"])
    x = x + _geglu(lp, h2)
    return x, (jnp.swapaxes(k, 0, 1), jnp.swapaxes(v, 0, 1))


def forward(cfg: ModelConfig, params: dict, batch: dict):
    tokens = batch["tokens"]
    b, s = tokens.shape
    dt = cfg.activation_dtype
    x = params["embed"]["table"].astype(dt)[tokens]
    cos, sin = L.rope_angles(jnp.arange(s), cfg.hd, cfg.rope_theta)

    def super_body(carry, lp):
        y = carry
        y, _ = _rec_block(cfg, lp["rec1"], y)
        y, _ = _rec_block(cfg, lp["rec2"], y)
        y, _ = _attn_block(cfg, lp["attn"], y, cos, sin)
        return y, ()

    x, _ = jax.lax.scan(L.maybe_remat(super_body, cfg.remat), x,
                        params["supers"], unroll=cfg.scan_unroll)
    if "tail" in params:
        def tail_body(carry, lp):
            y, _ = _rec_block(cfg, lp, carry)
            return y, ()
        x, _ = jax.lax.scan(L.maybe_remat(tail_body, cfg.remat), x,
                            params["tail"], unroll=cfg.scan_unroll)
    x = L.rms_norm(x, params["final_norm"])
    unemb = (params["embed"]["table"].astype(dt).T if cfg.tie_embeddings
             else params["unembed"].astype(dt))
    logits = jnp.einsum("bsd,dv->bsv", x, unemb)
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits, jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def cache_max_len(cfg: ModelConfig, seq_len: int) -> int:
    return min(seq_len, cfg.window or seq_len)


def abstract_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    ns, tail = n_supers(cfg)
    r = cfg.rglru_dim or cfg.d_model
    dt = cfg.activation_dtype
    kv = (ns, max_len, batch, cfg.n_kv_heads, cfg.hd)

    def rec_state(n):
        return {"h": jax.ShapeDtypeStruct((n, batch, r), jnp.float32),
                "conv": jax.ShapeDtypeStruct((n, batch, CONV_W - 1, r), dt)}

    cache = {"rec1": rec_state(ns), "rec2": rec_state(ns),
             "k": jax.ShapeDtypeStruct(kv, dt),
             "v": jax.ShapeDtypeStruct(kv, dt),
             "len": jax.ShapeDtypeStruct((), jnp.int32)}
    if tail:
        cache["tail"] = rec_state(tail)
    return cache


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        abstract_cache(cfg, batch, max_len))


def prefill(cfg: ModelConfig, params: dict, tokens: jax.Array,
            max_len: int):
    b, s = tokens.shape
    dt = cfg.activation_dtype
    x = params["embed"]["table"].astype(dt)[tokens]
    cos, sin = L.rope_angles(jnp.arange(s), cfg.hd, cfg.rope_theta)

    def super_body(carry, lp):
        y = carry
        y, st1 = _rec_block(cfg, lp["rec1"], y)
        y, st2 = _rec_block(cfg, lp["rec2"], y)
        y, (k, v) = _attn_block(cfg, lp["attn"], y, cos, sin)
        return y, (st1, st2, k, v)

    x, (st1, st2, kc, vc) = jax.lax.scan(super_body, x, params["supers"],
                                         unroll=cfg.scan_unroll)
    cache = {
        "rec1": {"h": st1[0], "conv": st1[1]},
        "rec2": {"h": st2[0], "conv": st2[1]},
        "len": jnp.asarray(min(s, max_len), jnp.int32),
    }
    if max_len >= s:
        pad = max_len - s
        kc = jnp.pad(kc, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        vc = jnp.pad(vc, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    else:
        kc, vc = kc[:, s - max_len:], vc[:, s - max_len:]
    cache["k"], cache["v"] = kc, vc
    if "tail" in params:
        def tail_body(carry, lp):
            y, st = _rec_block(cfg, lp, carry)
            return y, st
        x, st = jax.lax.scan(tail_body, x, params["tail"],
                             unroll=cfg.scan_unroll)
        cache["tail"] = {"h": st[0], "conv": st[1]}
    x = L.rms_norm(x, params["final_norm"])
    unemb = (params["embed"]["table"].astype(dt).T if cfg.tie_embeddings
             else params["unembed"].astype(dt))
    logits = jnp.einsum("bd,dv->bv", x[:, -1], unemb)
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits, cache


def decode_step(cfg: ModelConfig, params: dict, cache: dict,
                token: jax.Array, pos) -> tuple[jax.Array, dict]:
    b = token.shape[0]
    dt = cfg.activation_dtype
    hd = cfg.hd
    max_len = cache["k"].shape[1]
    slot = cache["len"] % max_len
    x = params["embed"]["table"].astype(dt)[token][:, None]
    cos, sin = L.rope_angles(jnp.asarray(pos).reshape(1), cfg.hd,
                             cfg.rope_theta)

    def rec_step(lp, x, h, conv):
        y, (h2, conv2) = _rec_block(cfg, lp, x, h0=h, conv_tail=conv)
        return y, h2, conv2

    def super_body(carry, xs):
        x, = carry
        lp, h1, c1, h2, c2, kc, vc = xs
        x, nh1, nc1 = rec_step(lp["rec1"], x, h1, c1)
        x, nh2, nc2 = rec_step(lp["rec2"], x, h2, c2)
        # local attention against ring-buffer cache
        h = L.rms_norm(x, lp["attn"]["norm"])
        q = jnp.einsum("bsd,dh->bsh", h, lp["attn"]["wq"].astype(dt)
                       ).reshape(b, 1, cfg.n_heads, hd)
        k = jnp.einsum("bsd,dh->bsh", h, lp["attn"]["wk"].astype(dt)
                       ).reshape(b, 1, cfg.n_kv_heads, hd)
        v = jnp.einsum("bsd,dh->bsh", h, lp["attn"]["wv"].astype(dt)
                       ).reshape(b, 1, cfg.n_kv_heads, hd)
        q = L.apply_rope(q, cos[None], sin[None])
        k = L.apply_rope(k, cos[None], sin[None])
        kc = jax.lax.dynamic_update_slice(kc, jnp.swapaxes(k, 0, 1),
                                          (slot, 0, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, jnp.swapaxes(v, 0, 1),
                                          (slot, 0, 0, 0))
        n_valid = jnp.minimum(cache["len"] + 1, max_len)
        o = L.decode_attention(q, jnp.swapaxes(kc, 0, 1),
                               jnp.swapaxes(vc, 0, 1), n_valid)
        o = jnp.einsum("bsh,hd->bsd", o.reshape(b, 1, cfg.n_heads * hd),
                       lp["attn"]["wo"].astype(dt))
        x = x + o
        hm = L.rms_norm(x, lp["attn"]["mlp_norm"])
        x = x + _geglu(lp["attn"], hm)
        return (x,), (nh1, nc1, nh2, nc2,
                      jnp.swapaxes(k, 0, 1)[0], jnp.swapaxes(v, 0, 1)[0])

    (x,), ys = jax.lax.scan(
        super_body, (x,),
        (params["supers"], cache["rec1"]["h"], cache["rec1"]["conv"],
         cache["rec2"]["h"], cache["rec2"]["conv"], cache["k"], cache["v"]),
        unroll=cfg.scan_unroll)
    nh1, nc1, nh2, nc2, k_new, v_new = ys
    new_cache = {
        "rec1": {"h": nh1, "conv": nc1},
        "rec2": {"h": nh2, "conv": nc2},
        "k": jax.lax.dynamic_update_slice(cache["k"], k_new[:, None],
                                          (0, slot, 0, 0, 0)),
        "v": jax.lax.dynamic_update_slice(cache["v"], v_new[:, None],
                                          (0, slot, 0, 0, 0)),
        "len": cache["len"] + 1,
    }
    if "tail" in params:
        def tail_body(carry, xs):
            x, = carry
            lp, h, c = xs
            y, nh, nc = rec_step(lp, x, h, c)
            return (y,), (nh, nc)
        (x,), (th, tc) = jax.lax.scan(
            tail_body, (x,),
            (params["tail"], cache["tail"]["h"], cache["tail"]["conv"]),
            unroll=cfg.scan_unroll)
        new_cache["tail"] = {"h": th, "conv": tc}
    x = L.rms_norm(x[:, 0], params["final_norm"])
    unemb = (params["embed"]["table"].astype(dt).T if cfg.tie_embeddings
             else params["unembed"].astype(dt))
    logits = jnp.einsum("bd,dv->bv", x, unemb)
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits, new_cache
