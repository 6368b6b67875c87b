"""Training launcher: ``python -m repro.launch.train --arch <id> ...``

Runs real steps on the available devices (CPU smoke scale by default,
TPU pods unchanged — the mesh adapts to jax.device_count()).  Wires every
substrate piece: data pipeline + prefetch, sharded train step, async
checkpointing, heartbeat and the recovery loop.
``train(args)`` is the whole run; at its end it counts the collectives
of its compiled step into ``repro.obs.collectives()``, from a compile of
their own.  ``main`` parses the command line, calls it and prints its
summary.  A run in which a step never succeeded raises, so the command
exits non-zero.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.data import DataConfig, Prefetcher, make_source
from repro.launch.compile_cache import place_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import zoo
from repro.models.common import (default_plan, partitioned,
                                 replicated_plan)
from repro.obs import (attention_paths, collectives, record_collectives,
                       tp_paths)
from repro.optim import AdamWConfig
from repro.sharding import named_sharding_tree
from repro.train import (CheckpointManager, Heartbeat, TrainConfig,
                         init_state, make_train_step, run_with_recovery,
                         state_specs)


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b", choices=list(ARCH_IDS))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the depth to this many layers (0: published)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--save-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    return ap


def build(args):
    cfg = get_config(args.arch, smoke=args.smoke)
    if args.layers:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    tcfg = TrainConfig(
        microbatches=args.microbatches,
        remat=not args.no_remat,
        optimizer=AdamWConfig(lr=args.lr, total_steps=args.steps,
                              warmup_steps=max(args.steps // 20, 1)))

    n_dev = jax.device_count()
    if n_dev >= 4:
        mesh = make_mesh((2, n_dev // 2), ("data", "model"))
        plan = default_plan()
    else:
        mesh = make_mesh((n_dev, 1), ("data", "model")) if n_dev > 1 \
            else make_mesh((1,), ("data",))
        plan = replicated_plan()
        plan.batch_axes = ("data",) if n_dev > 1 else ()
    return partitioned(cfg, plan), tcfg, mesh, plan


def init_placed_state(cfg, tcfg, mesh, plan, key) -> dict:
    """``init_state`` laid out on ``mesh`` per ``plan`` (call under
    ``jax.set_mesh(mesh)``)."""
    state = init_state(cfg, tcfg, key)
    if len(mesh.devices.ravel()) > 1:
        st_sh = named_sharding_tree(plan, mesh, state_specs(cfg, tcfg))
        state = jax.tree.map(jax.device_put, state, st_sh)
    return state


def jit_train_step(cfg, tcfg, plan):
    """The jitted step over ``cfg``'s partition (``plan`` is not read).
    The state is donated, so the new params and optimizer moments reuse
    the old ones' device memory."""
    return jax.jit(make_train_step(cfg, tcfg), donate_argnums=0)


def data_source(args, cfg):
    return make_source(DataConfig(
        seq_len=args.seq, global_batch=args.batch, vocab=cfg.vocab,
        frontend_tokens=cfg.n_frontend_tokens
        if zoo.needs_frontend(cfg) else 0,
        d_model=cfg.d_model))


def train(args) -> dict:
    """Run ``args.steps`` steps and save a final checkpoint; returns the
    summary with every step's loss."""
    cfg, tcfg, mesh, plan = build(args)
    print(f"arch={cfg.arch_id} layers={cfg.n_layers} "
          f"params={cfg.param_count():,} devices={jax.device_count()} "
          f"mesh={dict(mesh.shape)}")

    prefetch = Prefetcher(data_source(args, cfg))
    manager = CheckpointManager(args.ckpt_dir)
    heartbeat = Heartbeat(os.path.join(args.ckpt_dir, "heartbeat.json"))
    times: list[float] = []
    losses: dict[int, float] = {}
    batch_shapes: dict = {}
    paths0 = attention_paths().snapshot()
    tp0 = tp_paths().snapshot()

    try:
        with jax.set_mesh(mesh):
            state = init_placed_state(cfg, tcfg, mesh, plan,
                                      jax.random.PRNGKey(0))
            step_fn = jit_train_step(cfg, tcfg, plan)

            if args.resume:
                restored = manager.restore()
                if restored:
                    state, extra, start = restored
                    print(f"resumed from step {start}")

            def wrapped(state, batch, step):
                t0 = time.perf_counter()
                jb = {k: jnp.asarray(v) for k, v in batch.items()}
                state, metrics = step_fn(state, jb)
                jax.block_until_ready(metrics["loss"])
                dt = time.perf_counter() - t0
                times.append(dt)
                batch_shapes.update(
                    (k, jax.ShapeDtypeStruct(v.shape, v.dtype))
                    for k, v in jb.items())
                heartbeat.beat(step)
                return state, metrics

            def on_metrics(step, metrics):
                # keyed by step: a replayed step overwrites its own loss
                losses[step] = float(metrics["loss"])
                if step % 10 == 0 or step == args.steps - 1:
                    print(f"step {step:5d} loss {losses[step]:.4f} "
                          f"lr {float(metrics['lr']):.2e} "
                          f"gnorm {float(metrics['grad_norm']):.3f} "
                          f"dt {times[-1]*1e3:.0f}ms")

            state, stats = run_with_recovery(
                wrapped, state, n_steps=args.steps,
                save_every=args.save_every, manager=manager,
                data_prefetch=prefetch, on_metrics=on_metrics)
            manager.save(args.steps, state, extra={"final": True},
                         block=True)
            record_collectives(
                step_fn.lower(state, batch_shapes).compile().as_text())
    finally:
        prefetch.close()
    return {
        "arch": cfg.arch_id, "layers": cfg.n_layers, "steps": args.steps,
        "batch": args.batch, "seq": args.seq,
        "microbatches": tcfg.microbatches,
        "losses": [losses[i] for i in sorted(losses)],
        "mean_step_ms": 1e3 * sum(times) / max(len(times), 1),
        "failures": stats.failures, "restores": stats.restores,
        "compiles": stats.compiles,
        "attention_paths": {
            path: int(n - paths0.get(path, 0))
            for path, n in attention_paths().snapshot().items()},
        "tp_paths": {path: int(n - tp0.get(path, 0))
                     for path, n in tp_paths().snapshot().items()},
        "collectives": collectives().snapshot(),
    }


def main() -> None:
    place_compile_cache()
    print(json.dumps(train(parser().parse_args())))


if __name__ == "__main__":
    main()
