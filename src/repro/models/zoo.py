"""Family dispatch: one uniform interface over the six model families."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import (recurrentgemma, rwkv6, transformer, vlm, whisper)
from repro.models.common import ModelConfig, register_family

FAMILY_MODULES = {
    "dense": transformer,
    "moe": transformer,
    "ssm": rwkv6,
    "hybrid": recurrentgemma,
    "vlm": vlm,
    "audio": whisper,
}

for fam, mod in FAMILY_MODULES.items():
    register_family(fam, mod.abstract)


def module_for(cfg: ModelConfig):
    return FAMILY_MODULES[cfg.family]


def init(cfg: ModelConfig, key) -> dict:
    return module_for(cfg).init(cfg, key)


def abstract(cfg: ModelConfig) -> dict:
    return module_for(cfg).abstract(cfg)


def specs(cfg: ModelConfig) -> dict:
    return module_for(cfg).specs(cfg)


def forward(cfg: ModelConfig, params: dict, batch: dict):
    """batch: tokens (B,S) [+ frontend (B,T,d) for vlm/audio].
    Returns (logits, aux_loss)."""
    return module_for(cfg).forward(cfg, params, batch)


def needs_frontend(cfg: ModelConfig) -> bool:
    return cfg.family in ("vlm", "audio")


def abstract_cache(cfg: ModelConfig, batch: int, max_len: int):
    return module_for(cfg).abstract_cache(cfg, batch, max_len)


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    return module_for(cfg).init_cache(cfg, batch, max_len)


def cache_max_len(cfg: ModelConfig, seq_len: int) -> int:
    return module_for(cfg).cache_max_len(cfg, seq_len)


def prefill(cfg: ModelConfig, params: dict, batch: dict, max_len: int):
    mod = module_for(cfg)
    if needs_frontend(cfg):
        return mod.prefill(cfg, params, batch["tokens"], max_len,
                           frontend=batch.get("frontend"))
    return mod.prefill(cfg, params, batch["tokens"], max_len)


def decode_step(cfg: ModelConfig, params: dict, cache: dict,
                token: jax.Array, pos):
    return module_for(cfg).decode_step(cfg, params, cache, token, pos)


def loss_fn(cfg: ModelConfig, params: dict, batch: dict,
            aux_weight: float = 0.01):
    """Next-token cross-entropy (+ MoE aux).  batch needs "tokens" and
    "targets" (usually tokens shifted by one).

    The true-class logit is extracted with a one-hot contraction, NOT
    take_along_axis: a gather along the vocab dim of vocab-sharded logits
    forces GSPMD to replicate the full (B,S,V) tensor (involuntary full
    rematerialization), while the one-hot einsum partitions cleanly
    (local partial sum + small all-reduce)."""
    logits, aux = forward(cfg, params, batch)
    with jax.named_scope("head"):
        targets = batch["targets"]
        lf = logits.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(lf, axis=-1)
        onehot = jax.nn.one_hot(targets, lf.shape[-1], dtype=lf.dtype)
        true_logit = jnp.einsum("bsv,bsv->bs", lf, onehot)
        nll = lse - true_logit
        mask = batch.get("mask")
        if mask is not None:
            nll = nll * mask
            denom = jnp.maximum(jnp.sum(mask), 1.0)
        else:
            denom = nll.size
        loss = jnp.sum(nll) / denom
    return loss + aux_weight * aux, {"ce": loss, "aux": aux}
