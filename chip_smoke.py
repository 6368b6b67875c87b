"""Proof that the main path runs on a TPU: kernels, trainer and ServeEngine.

    python chip_smoke.py             # one chip: kernels, train, serve
    python chip_smoke.py --chips 4   # four chips: sharded train step only

Everything runs in this one process; it starts no children.  Without a
TPU it exits non-zero before any phase runs.  Each phase prints one JSON
line with what it checked; ``smoke_seconds`` is wall time of a cold smoke
run, compilation included, and not a measurement.  A failed check exits
non-zero.  The last line of a passing run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

Phases, at published widths with random weights made from a seed:

* kernels: flash attention at olmo-1b's q/k/v, RG-LRU at
  recurrentgemma-2b's width and WKV6 at rwkv6-1.6b's, compiled for the
  chip, each against its ``ref.py`` oracle in float32 at the tolerances
  ``tests/test_kernels.py`` uses for that dtype;
* train: olmo-1b cut to 8 of its 16 layers (the rest of the model at
  full width), 5 steps through ``repro.launch.train.train``;
* serve: olmo-1b at full depth, 8 seeded requests through
  ``repro.launch.serve.serve``;
* ``--chips 4``: one train step of the same cut olmo-1b on the launcher's
  (2, 2) mesh, and the same step on one device; losses and gradient norms
  must agree.
"""

from __future__ import annotations

import argparse
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.compile_cache import place_compile_cache  # noqa: E402

# olmo-1b's 16 layers at 16 B/param (fp32 params, grads, two moments) need
# 18.8 GB, over one chip's 16 GB: 8 layers with batch 2 x seq 2048 and one
# microbatch compile to 13.25 GiB for a v5e (memory_analysis of the step;
# batch 8 at 8 microbatches needs 16.6 GiB, because accumulating adds a
# second fp32 gradient).  lr is OLMo-1B's published peak.
TRAIN_ARGV = ["--arch", "olmo-1b", "--layers", "8", "--batch", "2",
              "--seq", "2048", "--microbatches", "1", "--steps", "5",
              "--lr", "4e-4", "--save-every", "0"]
SERVE_ARGV = ["--arch", "olmo-1b", "--requests", "8", "--slots", "4",
              "--prompt-len", "512", "--max-new", "16",
              "--max-len", str(512 + 16)]
SHARDED_ARGV = TRAIN_ARGV[:TRAIN_ARGV.index("--steps")] + ["--steps", "1"]

# published widths: olmo-1b's q/k/v (bf16), recurrentgemma-2b's RG-LRU,
# rwkv6-1.6b's WKV6 (f32), and their (atol, rtol) in tests/test_kernels.py
FLASH_SHAPE, FLASH_TOL = (1, 2048, 16, 128), (2e-2, 2e-2)
RGLRU_SHAPE, RGLRU_TOL = (1, 2048, 2560), (1e-5, 1e-5)
WKV6_SHAPE, WKV6_TOL = (1, 32, 2048, 64), (3e-4, 1e-3)
SEED = 0


def _compare(name: str, out, ref, atol: float, rtol: float) -> dict:
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    err = np.abs(out - ref)
    ok = bool(np.isfinite(out).all() and out.shape == ref.shape
              and (err <= atol + rtol * np.abs(ref)).all())
    return {"kernel": name, "shape": list(out.shape), "ok": ok,
            "max_abs_err": float(err.max()), "atol": atol, "rtol": rtol}


def kernels_phase() -> dict:
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.rglru.ops import rglru as rglru_op
    from repro.kernels.rglru.ref import rglru_ref
    from repro.kernels.rwkv6.ops import wkv6 as wkv6_op
    from repro.kernels.rwkv6.ref import wkv6_ref

    ks = jax.random.split(jax.random.PRNGKey(SEED), 10)
    f32 = jnp.float32
    results = []

    q, k, v = (jax.random.normal(kk, FLASH_SHAPE, jnp.bfloat16)
               for kk in ks[:3])
    out = flash_attention(q, k, v, causal=True)
    with jax.default_matmul_precision("highest"):
        ref = attention_ref(q.astype(f32), k.astype(f32), v.astype(f32),
                            causal=True)
    results.append(_compare("flash_attention", out, ref, *FLASH_TOL))

    a = jax.random.uniform(ks[3], RGLRU_SHAPE, minval=0.001, maxval=0.9995)
    x = jax.random.normal(ks[4], RGLRU_SHAPE)
    h, h_last = rglru_op(a, x)
    with jax.default_matmul_precision("highest"):
        href, hlref = rglru_ref(a, x)
    results.append(_compare("rglru", h, href, *RGLRU_TOL))
    results.append(_compare("rglru.h_last", h_last, hlref, *RGLRU_TOL))

    _, nh, _, d = WKV6_SHAPE
    r, kk, vv = (jax.random.normal(kx, WKV6_SHAPE) for kx in ks[5:8])
    w = jax.random.uniform(ks[8], WKV6_SHAPE, minval=0.5, maxval=0.999)
    u = jax.random.normal(ks[9], (nh, d)) * 0.5
    out, state = wkv6_op(r, kk, vv, w, u)
    with jax.default_matmul_precision("highest"):
        oref, sref = wkv6_ref(r, kk, vv, w, u)
    results.append(_compare("wkv6", out, oref, *WKV6_TOL))
    results.append(_compare("wkv6.state", state, sref, *WKV6_TOL))
    return {"ok": all(x["ok"] for x in results), "checks": results}


def _compiled_bytes(args) -> dict:
    """Device bytes of the train step as the compiler lays it out."""
    from repro.launch.train import build, jit_train_step
    from repro.train.trainer import abstract_state
    cfg, tcfg, mesh, plan = build(args)
    tok = jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32)
    with jax.set_mesh(mesh):
        m = jit_train_step(cfg, tcfg, plan).lower(
            abstract_state(cfg, tcfg),
            {"tokens": tok, "targets": tok}).compile().memory_analysis()
    out = {"argument": m.argument_size_in_bytes,
           "output": m.output_size_in_bytes,
           "alias": m.alias_size_in_bytes, "temp": m.temp_size_in_bytes}
    out["total"] = out["argument"] + out["output"] - out["alias"] \
        + out["temp"]
    return {"params": cfg.param_count(), "compiled_bytes": out}


def train_phase(argv=TRAIN_ARGV) -> dict:
    from repro.configs import get_config
    from repro.launch.train import parser, train

    ckpt = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    args = parser().parse_args(argv + ["--ckpt-dir", ckpt])
    published = get_config(args.arch, smoke=args.smoke).n_layers
    print(json.dumps({
        "phase": "train_plan", "arch": args.arch, "layers": args.layers,
        "published_layers": published, "batch": args.batch,
        "seq": args.seq, "microbatches": args.microbatches,
        **_compiled_bytes(args)}), flush=True)
    try:
        res = train(args)
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    losses = res["losses"]
    vocab = get_config(args.arch, smoke=args.smoke).vocab
    checks = {
        "steps_run": len(losses) == args.steps,
        "all_finite": all(math.isfinite(x) for x in losses),
        "first_near_ln_vocab": abs(losses[0] - math.log(vocab)) <= 1.0,
        "last_below_first": losses[-1] < losses[0],
        "no_failures": res["failures"] == 0,
    }
    stats = jax.devices()[0].memory_stats() or {}
    return {"ok": all(checks.values()), "checks": checks,
            "losses": losses, "ln_vocab": math.log(vocab),
            "failures": res["failures"],
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}


def serve_phase(argv=SERVE_ARGV) -> dict:
    from repro.configs import get_config
    from repro.launch.serve import parser, serve

    args = parser().parse_args(argv)
    summary, done = serve(args)
    vocab = get_config(args.arch, smoke=args.smoke).vocab
    checks = {
        "all_answered": sorted(r.rid for r in done)
        == list(range(args.requests)),
        "exact_token_count": all(len(r.output) == args.max_new
                                 for r in done),
        "in_vocab": all(0 <= t < vocab for r in done for t in r.output),
    }
    return {"ok": all(checks.values()), "checks": checks,
            "generated_tokens": summary["generated_tokens"],
            "prompt_lens": [len(r.prompt) for r in done],
            "smoke_wave_seconds": summary["wave_wall_s"]}


def sharded_phase(argv=SHARDED_ARGV) -> dict:
    import dataclasses

    from repro.launch.train import (build, data_source, init_placed_state,
                                    jit_train_step, parser)
    from repro.models.common import replicated_plan
    from repro.train import init_state

    args = parser().parse_args(argv)
    cfg, tcfg, mesh, plan = build(args)
    batch = {k: jnp.asarray(x)
             for k, x in data_source(args, cfg).batch_at(0).items()}
    key = jax.random.PRNGKey(SEED)
    with jax.set_mesh(mesh):
        state = init_placed_state(cfg, tcfg, mesh, plan, key)
        # the new state is dropped at once: both runs share device 0
        m = jit_train_step(cfg, tcfg, plan)(state, batch)[1]
        sharded = (float(m["loss"]), float(m["grad_norm"]))
    del state
    one = dataclasses.replace(cfg, batch_axes=())
    with jax.default_device(jax.devices()[0]):
        state = init_state(one, tcfg, key)
        m = jit_train_step(one, tcfg, replicated_plan())(state, batch)[1]
        single = (float(m["loss"]), float(m["grad_norm"]))
    rel = [abs(a - b) / abs(b) for a, b in zip(sharded, single)]
    return {"ok": all(x <= 1e-2 for x in rel) and all(
                math.isfinite(x) for x in sharded + single),
            "mesh": dict(mesh.shape), "layers": cfg.n_layers,
            "batch": args.batch, "seq": args.seq,
            "loss": {"sharded": sharded[0], "single": single[0],
                     "rel_diff": rel[0]},
            "grad_norm": {"sharded": sharded[1], "single": single[1],
                          "rel_diff": rel[1]}}


def main() -> int:
    place_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    chips = ap.parse_args().chips
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"chip_smoke: needs {chips} TPU chip(s), JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    phases = ([("sharded_vs_single", sharded_phase)] if chips == 4 else
              [("kernels", kernels_phase), ("train", train_phase),
               ("serve", serve_phase)])
    for name, fn in phases:
        t0 = time.perf_counter()
        rec = fn()
        rec = {"phase": name, **rec,
               "smoke_seconds": time.perf_counter() - t0}
        print(json.dumps(rec), flush=True)
        if not rec["ok"]:
            print(f"chip_smoke: phase {name} failed", file=sys.stderr)
            return 1
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
