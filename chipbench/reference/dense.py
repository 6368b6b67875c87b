"""Plain float32 reference of a dense decoder: OLMo (arXiv:2402.00838).

Per layer, pre-norm: non-parametric LayerNorm (no scale, no bias, eps
1e-5) -> q, k, v projections without bias -> rotary embedding on the two
halves of each head (theta from the configuration) -> causal softmax
attention, scale 1/sqrt(head_dim) -> output projection -> residual; then
LayerNorm -> SwiGLU MLP (silu(x Wg) * (x Wu)) Wd -> residual.  A final
LayerNorm, and logits from the tied embedding.  The loss is the mean
cross-entropy of every position's next token over all ``embedding_rows``
logits.

Departures from the paper, each the program's and stated in the
configuration file: the embedding has ``embedding_rows`` rows (the
vocabulary rounded up to 256) where OLMo-1B has 50304, and the padding
rows take part in the softmax; ``layers/attn_norm``, ``layers/mlp_norm``
and ``final_norm`` are held as parameters that nothing reads.

Layers run in a scan with each layer rematerialised, so that the
reference fits one chip at the cell's own batch; that changes no number.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference.common import F32, cross_entropy, fan_in_std, mm

# the CPU tests' size of the family: two layers at a 32nd of the width
TINY = {
    "name": "olmo-tiny", "arch": "olmo-1b", "smoke": True,
    "family": "dense", "num_hidden_layers": 2, "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "intermediate_size": 256, "vocab_size": 512, "embedding_rows": 512,
    "rope_theta": 10000.0, "layer_norm_eps": 1e-5,
    "tie_word_embeddings": True, "compute_dtype": "float32",
    "param_dtype": "float32"}


def program_settings(c: dict) -> dict:
    """The program's model settings (``ModelConfig`` attribute -> value)
    that the configuration file fixes, besides those every family has."""
    return {"n_kv_heads": c["num_key_value_heads"], "hd": c["head_dim"],
            "d_ff": c["intermediate_size"]}


def forward_macs(c: dict, seq: int) -> float:
    """Multiply-adds of the forward pass per token (``work.py``'s rules):
    the q, k, v, o and SwiGLU projections, causal attention over t + 1
    keys at position t, and the head."""
    d, hd = c["hidden_size"], c["head_dim"]
    hq, hkv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    proj = d * hq * 2 + d * hkv * 2 + 3 * d * c["intermediate_size"]
    attn = 2 * hq * (seq + 1) / 2          # q.k and p.v over (t+1) keys
    return c["num_hidden_layers"] * (proj + attn) + d * c["vocab_size"]


def layout(c: dict) -> dict:
    """The parameter tree: path -> (shape, init)."""
    n, d = c["num_hidden_layers"], c["hidden_size"]
    hq = c["num_attention_heads"] * c["head_dim"]
    hkv = c["num_key_value_heads"] * c["head_dim"]
    ff, rows = c["intermediate_size"], c["embedding_rows"]
    out = {
        "embed/table": ((rows, d), ("normal", 0.02)),
        "layers/attn_norm": ((n, d), ("const", 0.0)),
        "layers/mlp_norm": ((n, d), ("const", 0.0)),
        "final_norm": ((d,), ("const", 0.0)),
    }
    for name, shape in (("wq", (n, d, hq)), ("wk", (n, d, hkv)),
                        ("wv", (n, d, hkv)), ("wo", (n, hq, d)),
                        ("w_gate", (n, d, ff)), ("w_up", (n, d, ff)),
                        ("w_down", (n, ff, d))):
        out[f"layers/{name}"] = (shape, ("normal", fan_in_std(shape)))
    if not c["tie_word_embeddings"]:
        out["unembed"] = ((d, rows), ("normal", 0.02))
    return out


def layer_norm(x, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


def rope(x, theta):
    """x: (B, S, H, D); rotates the pair (x[i], x[i + D/2])."""
    s, d = x.shape[1], x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def loss(c: dict, params: dict, tokens, targets, control: bool = False):
    b, s = tokens.shape
    nh, nkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    eps, theta = c["layer_norm_eps"], c["rope_theta"]
    table = params["embed"]["table"]
    x = table[tokens]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lp):
        h = layer_norm(x, eps)
        q = mm("bsd,dh->bsh", h, lp["wq"], control).reshape(b, s, nh, hd)
        k = mm("bsd,dh->bsh", h, lp["wk"], control).reshape(b, s, nkv, hd)
        v = mm("bsd,dh->bsh", h, lp["wv"], control).reshape(b, s, nkv, hd)
        q, k = rope(q, theta), rope(k, theta)
        k = jnp.repeat(k, nh // nkv, axis=2)
        v = jnp.repeat(v, nh // nkv, axis=2)
        scores = mm("bqhd,bkhd->bhqk", q, k, control) / math.sqrt(hd)
        scores = jnp.where(causal, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        o = mm("bhqk,bkhd->bqhd", probs, v, control).reshape(b, s, nh * hd)
        x = x + mm("bsh,hd->bsd", o, lp["wo"], control)
        h = layer_norm(x, eps)
        act = (jax.nn.silu(mm("bsd,df->bsf", h, lp["w_gate"], control))
               * mm("bsd,df->bsf", h, lp["w_up"], control))
        return x + mm("bsf,fd->bsd", act, lp["w_down"], control), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["layers"])
    x = layer_norm(x, eps)
    if c["tie_word_embeddings"]:
        logits = mm("bsd,vd->bsv", x, table, control)
    else:
        logits = mm("bsd,dv->bsv", x, params["unembed"], control)
    return cross_entropy(logits, targets)
