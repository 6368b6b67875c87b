"""Whisper-small-style encoder-decoder (arXiv:2212.04356).

Per the task spec, only the transformer BACKBONE is modeled; the conv
frontend is a STUB — ``input_specs`` supplies precomputed frame embeddings
(B, n_frontend_tokens=1500, d_model) standing in for the mel->conv stack.

Encoder: bidirectional attention, sinusoidal positions, LayerNorm + GELU
MLP.  Decoder: causal self-attn + cross-attn over encoder output, learned
positions.  Serving caches decoder self-attn KV plus the precomputed
cross KV per layer; the encoder runs once at prefill.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.common import ModelConfig, TreeBuilder


def _attn_leaves(tb, prefix, n, cfg, kv=True):
    d, hd = cfg.d_model, cfg.hd
    tb.leaf(f"{prefix}/wq", (n, d, cfg.n_heads * hd),
            ("layers", "embed", "heads"))
    if kv:
        tb.leaf(f"{prefix}/wk", (n, d, cfg.n_kv_heads * hd),
                ("layers", "embed", "kv"))
        tb.leaf(f"{prefix}/wv", (n, d, cfg.n_kv_heads * hd),
                ("layers", "embed", "kv"))
    tb.leaf(f"{prefix}/wo", (n, cfg.n_heads * hd, d),
            ("layers", "heads", "embed"))


def _mlp_leaves(tb, prefix, n, cfg):
    d = cfg.d_model
    tb.leaf(f"{prefix}/w_up", (n, d, cfg.d_ff), ("layers", "embed", "ff"))
    tb.leaf(f"{prefix}/w_down", (n, cfg.d_ff, d), ("layers", "ff", "embed"))


def _build(cfg: ModelConfig, key, abstract: bool):
    tb = TreeBuilder(cfg, key, abstract=abstract)
    d = cfg.d_model
    ne, nd = cfg.encoder_layers, cfg.n_layers
    tb.leaf("embed/table", (cfg.padded_vocab, d), ("vocab", "table_d"), scale=0.02)
    tb.leaf("pos_embed", (4096, d), (None, "embed"), scale=0.01)

    # encoder
    tb.leaf("enc/attn_norm", (ne, d), ("layers", None), init="ones")
    tb.leaf("enc/mlp_norm", (ne, d), ("layers", None), init="ones")
    _attn_leaves(tb, "enc", ne, cfg)
    _mlp_leaves(tb, "enc", ne, cfg)
    tb.leaf("enc_final_norm", (d,), (None,), init="ones")

    # decoder: self + cross
    tb.leaf("dec/self_norm", (nd, d), ("layers", None), init="ones")
    tb.leaf("dec/cross_norm", (nd, d), ("layers", None), init="ones")
    tb.leaf("dec/mlp_norm", (nd, d), ("layers", None), init="ones")
    _attn_leaves(tb, "dec/self", nd, cfg)
    _attn_leaves(tb, "dec/cross", nd, cfg)
    _mlp_leaves(tb, "dec", nd, cfg)
    tb.leaf("final_norm", (d,), (None,), init="ones")
    return tb.build()


def init(cfg, key):
    return _build(cfg, key, abstract=False)[0]


def abstract(cfg):
    return _build(cfg, None, abstract=True)[0]


def specs(cfg):
    return _build(cfg, None, abstract=True)[1]


# ---------------------------------------------------------------------------

def _proj_heads(x, w, b, s, nh, hd):
    return jnp.einsum("bsd,dh->bsh", x, w.astype(x.dtype)
                      ).reshape(b, s, nh, hd)


def _mha(cfg, lp, xq, xkv=None, causal=False):
    """Self-attention over ``xq``, or cross-attention over ``xkv``."""
    dt = xq.dtype
    b, sq, _ = xq.shape
    src = xq if xkv is None else xkv
    sk = src.shape[1]
    hd = cfg.hd
    q = _proj_heads(xq, lp["wq"], b, sq, cfg.n_heads, hd)
    k = _proj_heads(src, lp["wk"], b, sk, cfg.n_kv_heads, hd)
    v = _proj_heads(src, lp["wv"], b, sk, cfg.n_kv_heads, hd)
    if xkv is None:
        o = L.attention(q, k, v, causal=causal, unroll=cfg.scan_unroll,
                        batch_axes=cfg.batch_axes, head_axes=cfg.head_axes)
    else:
        o = L.cross_attention(q, k, v, unroll=cfg.scan_unroll)
    return jnp.einsum("bsh,hd->bsd", o.reshape(b, sq, cfg.n_heads * hd),
                      lp["wo"].astype(dt)), k, v


def encode(cfg: ModelConfig, params: dict, frames: jax.Array) -> jax.Array:
    """frames: (B, T, d) stub embeddings -> encoder states (B,T,d)."""
    dt = cfg.activation_dtype
    s = frames.shape[1]
    pos = jnp.arange(s)
    half = cfg.d_model // 2
    freqs = jnp.exp(-jnp.arange(half) / (half - 1) * jnp.log(10000.0))
    ang = pos[:, None] * freqs[None]
    sinusoid = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)
    x = frames.astype(dt) + sinusoid[None].astype(dt)

    def body(carry, lp):
        y = L.constrain_batch(carry, cfg.batch_axes, cfg.seq_axes)
        h = L.layer_norm(y, lp["attn_norm"], None)
        o, _, _ = _mha(cfg, lp, h, causal=False)
        y = y + o
        h2 = L.layer_norm(y, lp["mlp_norm"], None)
        y = y + L.mlp_gelu(lp, h2)
        return y, ()

    x, _ = jax.lax.scan(L.maybe_remat(body, cfg.remat), x, params["enc"],
                        unroll=cfg.scan_unroll)
    return L.layer_norm(x, params["enc_final_norm"], None)


def _dec_layer(cfg, lp, x, enc, cos_sin=None):
    x = L.constrain_batch(x, cfg.batch_axes, cfg.seq_axes)
    h = L.layer_norm(x, lp["self_norm"], None)
    o, k, v = _mha(cfg, lp["self"], h, causal=True)
    x = x + o
    h2 = L.layer_norm(x, lp["cross_norm"], None)
    oc, xk, xv = _mha(cfg, lp["cross"], h2, enc)
    x = x + oc
    h3 = L.layer_norm(x, lp["mlp_norm"], None)
    x = x + L.mlp_gelu(lp, h3)
    return x, (k, v, xk, xv)


def forward(cfg: ModelConfig, params: dict, batch: dict):
    """batch: tokens (B,S_dec) + frontend (B, T_enc, d)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    dt = cfg.activation_dtype
    enc = encode(cfg, params, batch["frontend"])
    x = params["embed"]["table"].astype(dt)[tokens]
    npos = params["pos_embed"].shape[0]
    x = x + params["pos_embed"].astype(dt)[jnp.arange(s) % npos]

    def body(carry, lp):
        y, _ = _dec_layer(cfg, lp, carry, enc)
        return y, ()

    x, _ = jax.lax.scan(L.maybe_remat(body, cfg.remat), x, params["dec"],
                        unroll=cfg.scan_unroll)
    x = L.layer_norm(x, params["final_norm"], None)
    logits = jnp.einsum("bsd,vd->bsv", x,
                        params["embed"]["table"].astype(dt))  # tied unembed
    return logits, jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def cache_max_len(cfg: ModelConfig, seq_len: int) -> int:
    return seq_len


def abstract_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    dt = cfg.activation_dtype
    nd = cfg.n_layers
    kv = (nd, max_len, batch, cfg.n_kv_heads, cfg.hd)
    xkv = (nd, cfg.n_frontend_tokens, batch, cfg.n_kv_heads, cfg.hd)
    return {"k": jax.ShapeDtypeStruct(kv, dt),
            "v": jax.ShapeDtypeStruct(kv, dt),
            "xk": jax.ShapeDtypeStruct(xkv, dt),
            "xv": jax.ShapeDtypeStruct(xkv, dt),
            "len": jax.ShapeDtypeStruct((), jnp.int32)}


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        abstract_cache(cfg, batch, max_len))


def prefill(cfg: ModelConfig, params: dict, tokens: jax.Array,
            max_len: int, frontend: jax.Array | None = None):
    b, s = tokens.shape
    dt = cfg.activation_dtype
    frames = (frontend if frontend is not None else jnp.zeros(
        (b, cfg.n_frontend_tokens, cfg.d_model))).astype(dt)
    enc = encode(cfg, params, frames)
    x = params["embed"]["table"].astype(dt)[tokens]
    npos = params["pos_embed"].shape[0]
    x = x + params["pos_embed"].astype(dt)[jnp.arange(s) % npos]

    def body(carry, lp):
        y, (k, v, xk, xv) = _dec_layer(cfg, lp, carry, enc)
        return y, (jnp.swapaxes(k, 0, 1), jnp.swapaxes(v, 0, 1),
                   jnp.swapaxes(xk, 0, 1), jnp.swapaxes(xv, 0, 1))

    x, (kc, vc, xk, xv) = jax.lax.scan(body, x, params["dec"],
                                       unroll=cfg.scan_unroll)
    pad = max_len - s
    kc = jnp.pad(kc, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    vc = jnp.pad(vc, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    x = L.layer_norm(x, params["final_norm"], None)
    logits = jnp.einsum("bd,vd->bv", x[:, -1],
                        params["embed"]["table"].astype(dt))
    cache = {"k": kc, "v": vc, "xk": xk, "xv": xv,
             "len": jnp.asarray(s, jnp.int32)}
    return logits, cache


def decode_step(cfg: ModelConfig, params: dict, cache: dict,
                token: jax.Array, pos) -> tuple[jax.Array, dict]:
    b = token.shape[0]
    dt = cfg.activation_dtype
    hd = cfg.hd
    slot = cache["len"]
    x = params["embed"]["table"].astype(dt)[token][:, None]
    npos = params["pos_embed"].shape[0]
    x = x + params["pos_embed"].astype(dt)[jnp.asarray(pos) % npos][None, None]

    def body(carry, xs):
        x, = carry
        lp, kc, vc, xk, xv = xs
        h = L.layer_norm(x, lp["self_norm"], None)
        q = _proj_heads(h, lp["self"]["wq"], b, 1, cfg.n_heads, hd)
        k = _proj_heads(h, lp["self"]["wk"], b, 1, cfg.n_kv_heads, hd)
        v = _proj_heads(h, lp["self"]["wv"], b, 1, cfg.n_kv_heads, hd)
        kc = jax.lax.dynamic_update_slice(kc, jnp.swapaxes(k, 0, 1),
                                          (slot, 0, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, jnp.swapaxes(v, 0, 1),
                                          (slot, 0, 0, 0))
        o = L.decode_attention(q, jnp.swapaxes(kc, 0, 1),
                               jnp.swapaxes(vc, 0, 1), cache["len"] + 1)
        x = x + jnp.einsum("bsh,hd->bsd",
                           o.reshape(b, 1, cfg.n_heads * hd),
                           lp["self"]["wo"].astype(dt))
        h2 = L.layer_norm(x, lp["cross_norm"], None)
        q2 = _proj_heads(h2, lp["cross"]["wq"], b, 1, cfg.n_heads, hd)
        o2 = L.cross_attention(q2, jnp.swapaxes(xk, 0, 1),
                               jnp.swapaxes(xv, 0, 1))
        x = x + jnp.einsum("bsh,hd->bsd",
                           o2.reshape(b, 1, cfg.n_heads * hd),
                           lp["cross"]["wo"].astype(dt))
        h3 = L.layer_norm(x, lp["mlp_norm"], None)
        x = x + L.mlp_gelu(lp, h3)
        return (x,), (jnp.swapaxes(k, 0, 1)[0], jnp.swapaxes(v, 0, 1)[0])

    (x,), (k_new, v_new) = jax.lax.scan(
        body, (x,), (params["dec"], cache["k"], cache["v"],
                     cache["xk"], cache["xv"]), unroll=cfg.scan_unroll)
    new_cache = dict(cache)
    new_cache["k"] = jax.lax.dynamic_update_slice(
        cache["k"], k_new[:, None], (0, slot, 0, 0, 0))
    new_cache["v"] = jax.lax.dynamic_update_slice(
        cache["v"], v_new[:, None], (0, slot, 0, 0, 0))
    new_cache["len"] = cache["len"] + 1
    x = L.layer_norm(x[:, 0], params["final_norm"], None)
    logits = jnp.einsum("bd,vd->bv", x, params["embed"]["table"].astype(dt))
    return logits, new_cache
