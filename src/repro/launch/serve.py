"""Serving launcher: ``python -m repro.launch.serve --arch <id> ...``

Loads (or random-initializes) a model and drives the batched serving
engine over a synthetic request stream, reporting throughput and slot
utilization.  ``serve(args)`` is the whole run; ``main`` parses the
command line, calls it and prints its summary.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.launch.compile_cache import place_compile_cache
from repro.models import zoo
from repro.serving import Request, ServeEngine
from repro.train import CheckpointManager


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b", choices=list(ARCH_IDS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--prompt-len", type=int, default=16,
                    help="prompts are seeded draws of half to this length")
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore params from a training checkpoint")
    return ap


def serve(args) -> tuple[dict, list[Request]]:
    """Serve ``args.requests`` seeded requests; returns the summary and
    the finished requests."""
    cfg = get_config(args.arch, smoke=args.smoke)
    if args.ckpt_dir:
        restored = CheckpointManager(args.ckpt_dir).restore()
        if restored is None:
            raise SystemExit(f"no checkpoint in {args.ckpt_dir}")
        params = restored[0]["params"]
        params = jax.tree.map(jax.numpy.asarray, params)
        print(f"restored params from step {restored[2]}")
    else:
        params = zoo.init(cfg, jax.random.PRNGKey(0))

    engine = ServeEngine(cfg, params, n_slots=args.slots,
                         max_len=args.max_len)
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        plen = int(rng.integers(args.prompt_len // 2, args.prompt_len + 1))
        engine.submit(Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab, size=plen).astype(np.int32),
            max_new_tokens=args.max_new))

    t0 = time.perf_counter()
    done = engine.run()
    wall = time.perf_counter() - t0
    total_tokens = sum(len(r.output) for r in done)
    return {
        "arch": cfg.arch_id,
        "requests": len(done),
        "generated_tokens": total_tokens,
        "tokens_per_s": total_tokens / wall,
        "mean_slot_utilization": engine.mean_slot_utilization,
        "waves": len(engine.stats),
        "wave_wall_s": [w.wall_s for w in engine.stats],
    }, done


def main() -> None:
    place_compile_cache()
    print(json.dumps(serve(parser().parse_args())[0], indent=1))


if __name__ == "__main__":
    main()
