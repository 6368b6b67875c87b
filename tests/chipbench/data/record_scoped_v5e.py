"""Record ``scoped_v5e.xplane.pb`` and ``scoped_v5e.hlo.txt`` on one TPU.

    python3 tests/chipbench/data/record_scoped_v5e.py [out_dir]

A jitted two-layer scan whose body is split into an ``attention`` and an
``mlp`` scope, run for three steps the way the benchmark's loop runs the
program's: a ``repro.step`` span around each step, holding a
``repro.data`` span (a 5 ms sleep in place of ``batch_at``) and then the
benchmark's ``train`` span with its ``dispatch`` and ``wait``.  The HLO
text is the compiled program's, without the stack-frame tables (they
hold source paths); in the trace the checkout's path is blanked.  Both
are written beside this file, or into ``out_dir``.  Without a TPU it
exits non-zero and writes nothing.
"""

import os
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

HERE = Path(__file__).resolve().parent
TABLES = re.compile(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                    r"(?:(?!%|ENTRY).*\n)*", re.M)


def layers(x, ws):
    def body(h, w):
        with jax.named_scope("attention"):
            h = jnp.tanh(h @ w)
        with jax.named_scope("mlp"):
            h = h + jax.nn.relu(h @ w.T)
        return h, jnp.sum(h)
    return jax.lax.scan(body, x, ws)


def main() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if jax.devices()[0].platform != "tpu":
        print("record: needs a TPU", file=sys.stderr)
        return 1
    step = jax.jit(layers)
    x = jnp.ones((512, 1024), jnp.float32)
    ws = jnp.full((2, 1024, 1024), 1e-3, jnp.float32)
    jax.block_until_ready(step(x, ws))
    hlo = step.lower(x, ws).compile().as_text()
    out = Path(tempfile.mkdtemp())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(out), profiler_options=opts)
    for i in range(3):
        with jax.profiler.StepTraceAnnotation("repro.step", step_num=i):
            with jax.profiler.TraceAnnotation("repro.data"):
                with jax.profiler.TraceAnnotation("data"):
                    time.sleep(0.005)
            with jax.profiler.StepTraceAnnotation("train", step_num=i):
                with jax.profiler.TraceAnnotation("dispatch"):
                    h, sums = step(x, ws)
                with jax.profiler.TraceAnnotation("wait"):
                    jax.block_until_ready(sums)
    jax.profiler.stop_trace()
    (trace,) = out.glob("**/*.xplane.pb")
    dest = Path(sys.argv[1]) if len(sys.argv) > 1 else HERE
    dest.mkdir(parents=True, exist_ok=True)
    # source locations name the checkout: blank it, keeping every length
    root = str(HERE.parents[2]).encode() + b"/"
    (dest / "scoped_v5e.xplane.pb").write_bytes(
        trace.read_bytes().replace(root, b"#" * len(root)))
    (dest / "scoped_v5e.hlo.txt").write_text(TABLES.sub("", hlo))
    shutil.rmtree(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
