"""The train step: ``make_train_step`` builds the jittable
(state, batch) -> (state, metrics) function the launcher and the dry-run
both lower.

* microbatch gradient accumulation via ``lax.scan`` (the microbatch count
  trades activation memory against per-step overhead);
* per-layer activation checkpointing: ``TrainConfig.remat`` wraps every
  layer-scan body of the model in ``jax.checkpoint``;
* AdamW with schedule and global-norm clip, under the ``optimizer``
  named scope.

The training state is a plain dict {params, opt}, so checkpointing stays
structural.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models import zoo
from repro.models.common import ModelConfig
from repro.optim import (AdamWConfig, adamw_update, init_opt_state,
                         abstract_opt_state)

Params = Any


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    microbatches: int = 1
    # the one remat switch: make_train_step turns it into the model's
    # per-layer ModelConfig.remat = "full"
    remat: bool = True
    accum_dtype: str = "float32"     # grad-accumulator dtype (bf16 halves
                                     # the accumulation buffer: needed to
                                     # fit llama3-405b on one pod)
    aux_weight: float = 0.01
    optimizer: AdamWConfig = dataclasses.field(default_factory=AdamWConfig)


def init_state(cfg: ModelConfig, tcfg: TrainConfig, key) -> dict:
    params = zoo.init(cfg, key)
    return {"params": params,
            "opt": init_opt_state(tcfg.optimizer, params)}


def abstract_state(cfg: ModelConfig, tcfg: TrainConfig) -> dict:
    params = zoo.abstract(cfg)
    return {"params": params,
            "opt": abstract_opt_state(tcfg.optimizer, params)}


def state_specs(cfg: ModelConfig, tcfg: TrainConfig) -> dict:
    """Logical-axes tree matching init_state's structure."""
    pspecs = zoo.specs(cfg)
    return {"params": pspecs,
            "opt": {"mu": pspecs, "nu": pspecs, "step": ()}}


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig,
                    batch_axes: tuple[str, ...] | None = None
                    ) -> Callable[[dict, dict], tuple[dict, dict]]:
    """The microbatched tree gets an explicit sharding constraint over
    the mesh axes the batch is sharded over, ``cfg.batch_axes`` (or
    ``batch_axes`` where given): the (B,) -> (n_micro, B/n) reshape is
    ambiguous to GSPMD and silently de-shards the batch otherwise (found
    in the first dry-run)."""
    if batch_axes is None:
        batch_axes = cfg.batch_axes
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

        with jax.named_scope("optimizer"):
            new_params, new_opt, om = adamw_update(
                tcfg.optimizer, grads, state["opt"], params)
        return {"params": new_params, "opt": new_opt}, {"loss": loss, **om}

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
