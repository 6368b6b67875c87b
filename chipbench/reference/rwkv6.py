"""Plain float32 reference of RWKV6 "Finch" (arXiv:2404.05892).

Per layer: x += TimeMix(RMSNorm(x)); x += ChannelMix(RMSNorm(x)), with
RMSNorm(x) = x / sqrt(mean(x^2) + 1e-6) * (1 + scale).

TimeMix, for input x with token shift x' (x one position back, zero at
the first) and delta = x' - x:
  m_i = mu_i + tanh(x A) B_i           (A: d x 5r, B_i: r x d, i = w,k,v,r,g)
  x_i = x + delta * m_i
  r, k, v, g = x_r Wr, x_k Wk, x_v Wv, x_g Wg
  w = exp(-exp(clip(w0 + tanh(x_w A_w) B_w, -10, 4)))      (decay in (0,1))
  per head, with state S (D x D) starting at 0:
    out_t = r_t (S + diag(u) k_t^T v_t);  S = diag(w_t) S + k_t^T v_t
  o = out / sqrt(mean_head(out^2) + 1e-6) * ln_x;   TimeMix = (o * silu(g)) Wo
ChannelMix: x_k = x + delta * mu_k, x_r = x + delta * mu_r,
  (sigmoid(x_r Wr) * (relu(x_k Wk)^2 Wv)).
Logits from a final RMSNorm and the untied head; the loss is the mean
next-token cross-entropy.

The recurrence runs token by token, as written above, in a scan over
chunks of 64 tokens whose inner scan is rematerialised, so that its
backward pass keeps one state per chunk.

Departures from the paper, each the program's: RMSNorm with (1 + scale)
where the paper has LayerNorm, and no LayerNorm after the embedding; the
ddlerp's low-rank input is x itself, not x + delta * mu_x; the per-head
normalisation of the WKV output has no mean and no bias.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.common import (F32, cross_entropy, fan_in_std, mm,
                                        operand)

LORA = 32          # rank of the ddlerp and decay low-rank maps
CHUNK = 64         # tokens per rematerialised block of the recurrence


def layout(c: dict) -> dict:
    n, d = c["num_hidden_layers"], c["hidden_size"]
    nh, ff, rows = c["num_attention_heads"], c["intermediate_size"], \
        c["embedding_rows"]
    hd = d // nh
    out = {
        "embed/table": ((rows, d), ("normal", 0.02)),
        "unembed": ((d, rows), ("normal", 0.02)),
        "final_norm": ((d,), ("normal", 0.1)),
        "layers/tm_norm": ((n, d), ("normal", 0.1)),
        "layers/cm_norm": ((n, d), ("normal", 0.1)),
        "layers/tm_mu": ((n, 5, d), ("uniform", 0.0, 1.0)),
        "layers/tm_lora_b": ((n, 5, LORA, d), ("normal", 0.1)),
        "layers/w0": ((n, d), ("decay_speed",)),
        "layers/w_lora_b": ((n, LORA, d), ("normal", 0.1)),
        "layers/u": ((n, nh, hd), ("uniform", 0.0, 1.0)),
        "layers/ln_x": ((n, d), ("one_plus_normal", 0.1)),
        "layers/cm_mu": ((n, 2, d), ("uniform", 0.0, 1.0)),
    }
    for name, shape in (("tm_lora_a", (n, d, 5 * LORA)),
                        ("w_lora_a", (n, d, LORA)),
                        ("wr", (n, d, d)), ("wk", (n, d, d)),
                        ("wv", (n, d, d)), ("wg", (n, d, d)),
                        ("wo", (n, d, d)), ("cm_wk", (n, d, ff)),
                        ("cm_wv", (n, ff, d)), ("cm_wr", (n, d, d))):
        out[f"layers/{name}"] = (shape, ("normal", fan_in_std(shape)))
    return out


def rms_norm(x, scale):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6) \
        * (1.0 + scale)


def shift(x):
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def wkv(r, k, v, w, u):
    """r, k, v, w: (B, S, H, D); u: (H, D).  Token by token."""
    b, s, h, d = r.shape
    size = CHUNK if s % CHUNK == 0 else s
    n = s // size

    def token(state, xs):
        rt, kt, vt, wt = xs                                 # (B, H, D)
        kv = kt[..., :, None] * vt[..., None, :]            # (B, H, D, D)
        out = mm("bhd,bhdv->bhv", rt, state + u[None, :, :, None] * kv)
        return wt[..., :, None] * state + kv, out

    @jax.checkpoint
    def chunk(state, xs):
        return jax.lax.scan(token, state, xs)

    def blocks(z):              # (B, S, H, D) -> (n, CHUNK, B, H, D)
        return jnp.moveaxis(z, 1, 0).reshape(n, size, b, h, d)

    state0 = jnp.zeros((b, h, d, d), F32)
    _, out = jax.lax.scan(chunk, state0,
                          tuple(blocks(z) for z in (r, k, v, w)))
    return jnp.moveaxis(out.reshape(s, b, h, d), 0, 1)


def time_mix(lp, x, nh, control):
    b, s, d = x.shape
    hd = d // nh
    delta = shift(x) - x
    lora = jnp.tanh(mm("bsd,dr->bsr", x, lp["tm_lora_a"], control))
    amt = lp["tm_mu"][None, None] + mm(
        "bskr,krd->bskd", lora.reshape(b, s, 5, LORA), lp["tm_lora_b"],
        control)
    mixed = x[:, :, None] + delta[:, :, None] * amt
    xw, xk, xv, xr, xg = (mixed[:, :, i] for i in range(5))
    r = mm("bsd,dh->bsh", xr, lp["wr"], control)
    k = mm("bsd,dh->bsh", xk, lp["wk"], control)
    v = mm("bsd,dh->bsh", xv, lp["wv"], control)
    g = mm("bsd,dh->bsh", xg, lp["wg"], control)
    wl = mm("bsr,rd->bsd",
            jnp.tanh(mm("bsd,dr->bsr", xw, lp["w_lora_a"], control)),
            lp["w_lora_b"], control)
    w = jnp.exp(-jnp.exp(jnp.clip(lp["w0"][None, None] + wl, -10.0, 4.0)))

    def heads(z):
        return z.reshape(b, s, nh, hd)

    out = wkv(*(heads(operand(z, control)) for z in (r, k, v)),
              heads(w), lp["u"])
    out = out / jnp.sqrt(jnp.mean(out * out, axis=-1, keepdims=True) + 1e-6)
    out = out.reshape(b, s, d) * lp["ln_x"] * jax.nn.silu(g)
    return mm("bsh,hd->bsd", out, lp["wo"], control)


def channel_mix(lp, x, control):
    delta = shift(x) - x
    xk = x + delta * lp["cm_mu"][0]
    xr = x + delta * lp["cm_mu"][1]
    k = jnp.square(jax.nn.relu(mm("bsd,df->bsf", xk, lp["cm_wk"], control)))
    r = jax.nn.sigmoid(mm("bsd,de->bse", xr, lp["cm_wr"], control))
    return r * mm("bsf,fd->bsd", k, lp["cm_wv"], control)


def loss(c: dict, params: dict, tokens, targets, control: bool = False):
    nh = c["num_attention_heads"]
    x = params["embed"]["table"][tokens]

    def layer(x, lp):
        x = x + time_mix(lp, rms_norm(x, lp["tm_norm"]), nh, control)
        x = x + channel_mix(lp, rms_norm(x, lp["cm_norm"]), control)
        return x, None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["layers"])
    x = rms_norm(x, params["final_norm"])
    logits = mm("bsd,dv->bsv", x, params["unembed"], control)
    return cross_entropy(logits, targets)
