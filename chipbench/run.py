"""Run one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is found by name in ``BENCHMARK.json``.  One process, no
children.  Without a TPU, or with another number of chips than the cell
asks for, it exits non-zero and prints no result.  The last line of
standard output is the result (``chipbench/result.py``).
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if args.seed < 0:
        print("run: --seed must be a whole number >= 0", file=sys.stderr)
        return 2
    from chipbench import result, spec
    cell = spec.resolve(args.workload)
    # libtpu logs to /tmp/tpu_logs unless told otherwise: keep the run's
    # writes inside its checkout and its own directories
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro.launch.compile_cache import place_compile_cache
    place_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != cell.chips:
        print(f"run: {cell.name} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    kind = spec.kind_module(cell.traffic["kind"])
    out = kind.run(cell, args.seed, args.seconds, bool(args.trace), T0)
    result.emit(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
