"""Training loop substrate: train-step builder with gradient accumulation,
remat policy, sharded state, and the paper-technique hooks.

``make_train_step`` builds the jittable (state, batch) -> (state, metrics)
function the launcher and the dry-run both lower:

* microbatch gradient accumulation via ``lax.scan`` (the microbatch count
  is one of the autotuner's knobs — it trades activation memory against
  per-step overhead, DESIGN.md A2);
* activation checkpointing via ``jax.checkpoint`` with a configurable
  policy around the per-microbatch loss (applies through the layer scan);
* gradient compression with error feedback before the optimizer (the
  cross-pod wire-byte saving is accounted in the roofline DCI term —
  XLA's in-jit DP reduction itself stays dense; see optim/compression.py);
* AdamW with schedule + global-norm clip.

TrainState is a plain dict {params, opt, error?} so checkpointing stays
structural.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models import zoo
from repro.models.common import ModelConfig
from repro.optim import (AdamWConfig, CompressionConfig, adamw_update,
                         compress, init_error_state, init_opt_state,
                         abstract_opt_state)

Params = Any


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    microbatches: int = 1
    remat: bool = True
    remat_policy: str = "dots"       # nothing | dots | everything
    accum_dtype: str = "float32"     # grad-accumulator dtype (bf16 halves
                                     # the accumulation buffer: needed to
                                     # fit llama3-405b on one pod)
    aux_weight: float = 0.01
    optimizer: AdamWConfig = dataclasses.field(default_factory=AdamWConfig)
    compression: CompressionConfig = dataclasses.field(
        default_factory=CompressionConfig)


def init_state(cfg: ModelConfig, tcfg: TrainConfig, key) -> dict:
    params = zoo.init(cfg, key)
    state = {"params": params,
             "opt": init_opt_state(tcfg.optimizer, params)}
    if tcfg.compression.scheme != "none" and tcfg.compression.ef:
        state["error"] = init_error_state(params)
    return state


def abstract_state(cfg: ModelConfig, tcfg: TrainConfig) -> dict:
    params = zoo.abstract(cfg)
    state = {"params": params,
             "opt": abstract_opt_state(tcfg.optimizer, params)}
    if tcfg.compression.scheme != "none" and tcfg.compression.ef:
        state["error"] = jax.tree.map(
            lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), params)
    return state


def state_specs(cfg: ModelConfig, tcfg: TrainConfig) -> dict:
    """Logical-axes tree matching init_state's structure."""
    pspecs = zoo.specs(cfg)
    out = {"params": pspecs,
           "opt": {"mu": pspecs, "nu": pspecs, "step": ()}}
    if tcfg.compression.scheme != "none" and tcfg.compression.ef:
        out["error"] = pspecs
    return out


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig,
                    batch_axes: tuple[str, ...] | None = None
                    ) -> Callable[[dict, dict], tuple[dict, dict]]:
    """``batch_axes``: mesh axes the batch dim is sharded over; when set,
    the microbatched tree gets an explicit sharding constraint — the
    (B,) -> (n_micro, B/n) reshape is ambiguous to GSPMD and silently
    de-shards the batch otherwise (found in the first dry-run)."""
    n_micro = tcfg.microbatches
    # per-LAYER remat (jax.checkpoint around the models' scan bodies):
    # checkpointing the whole loss would still stack full per-layer
    # backward residuals inside the layer scan (found in the first
    # dry-run: 128 GiB of stacked attention residuals for olmo-1b)
    if tcfg.remat:
        cfg = dataclasses.replace(cfg, remat="full")

    def micro_loss(params, mb):
        loss, metrics = zoo.loss_fn(cfg, params, mb,
                                    aux_weight=tcfg.aux_weight)
        return loss, metrics

    grad_fn = jax.value_and_grad(micro_loss, has_aux=True)

    def train_step(state: dict, batch: dict) -> tuple[dict, dict]:
        params = state["params"]

        def reshape_micro(x):
            b = x.shape[0]
            assert b % n_micro == 0, (b, n_micro)
            return x.reshape(n_micro, b // n_micro, *x.shape[1:])

        micro = jax.tree.map(reshape_micro, batch)
        if batch_axes:
            from jax.sharding import PartitionSpec as P

            def constrain(x):
                spec = P(None, tuple(batch_axes),
                         *([None] * (x.ndim - 2)))
                return jax.lax.with_sharding_constraint(x, spec)

            micro = jax.tree.map(constrain, micro)

        acc_dt = jnp.dtype(tcfg.accum_dtype)

        def acc_body(carry, mb):
            gsum, lsum = carry
            (loss, metrics), grads = grad_fn(params, mb)
            gsum = jax.tree.map(
                lambda a, g: a + g.astype(acc_dt), gsum, grads)
            return (gsum, lsum + loss), metrics

        gzero = jax.tree.map(
            lambda p: jnp.zeros(p.shape, acc_dt), params)
        (gsum, lsum), _ = jax.lax.scan(acc_body, (gzero, 0.0), micro)
        grads = jax.tree.map(
            lambda g: (g.astype(jnp.float32) / n_micro).astype(acc_dt), gsum)
        loss = lsum / n_micro

        metrics = {"loss": loss}
        if "error" in state:
            grads, new_error, cm = compress(
                tcfg.compression, grads, state["error"])
            metrics.update(cm)
        with jax.named_scope("optimizer"):
            new_params, new_opt, om = adamw_update(
                tcfg.optimizer, grads, state["opt"], params)
        metrics.update(om)
        new_state = {"params": new_params, "opt": new_opt}
        if "error" in state:
            new_state["error"] = new_error
        return new_state, metrics

    return train_step


def make_eval_step(cfg: ModelConfig, tcfg: TrainConfig):
    def eval_step(state: dict, batch: dict) -> dict:
        loss, metrics = zoo.loss_fn(cfg, state["params"], batch,
                                    aux_weight=tcfg.aux_weight)
        return {"loss": loss, **metrics}
    return eval_step


def make_prefill_step(cfg: ModelConfig, max_len: int):
    def prefill_step(params: dict, batch: dict):
        return zoo.prefill(cfg, params, batch, max_len)
    return prefill_step


def make_serve_step(cfg: ModelConfig):
    """One decode step: (params, cache, token, pos) -> (logits, cache)."""
    def serve_step(params: dict, cache: dict, token, pos):
        return zoo.decode_step(cfg, params, cache, token, pos)
    return serve_step
