"""Training cells: the program's recovery loop over its compiled step, as
``repro.launch.train.train`` runs it, timed for a window of seconds.

Set-up builds the mesh and plan (``build``), the state in one jitted call
(the benchmark's weights from the seed, the program's optimizer state),
the jitted step and the data source with the seed; then it drives the
first ``checked_steps`` steps through the same loop and feed as the
window, reading what the check needs after the first and the last of
them.  The window starts with the next step and closes at the first step
that would start after ``seconds``; a traced run then traces
``trace_steps`` more.  After the window the program's state is freed and
the reference trains the checked steps from the same seed.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import math
import shutil
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import check, data, reference, trace_reduce, work
from chipbench.reference.common import key_from_seed, leaf_norms, make_weights
from chipbench.spec import ROOT, Cell, metric_reader

TRACE_DIR = ROOT / ".chipbench" / "trace"


class WindowClosed(BaseException):
    """Ends the loop from inside it.  ``run_with_recovery`` retries an
    ``Exception``; this is not one, so it passes through untouched."""


class ConfigMismatch(ValueError):
    """The program does not run the configuration file as written."""


def program_args(cell: Cell) -> argparse.Namespace:
    """``repro.launch.train``'s arguments for the cell."""
    c, t = cell.config, cell.traffic
    return argparse.Namespace(
        arch=c["arch"], smoke=bool(c.get("smoke", False)),
        layers=c["num_hidden_layers"], steps=t["optimizer"]["total_steps"],
        batch=t["global_batch"], seq=t["seq_len"], lr=t["optimizer"]["lr"],
        microbatches=t["microbatches"], no_remat=not t["remat"])


def check_program_config(cell: Cell, cfg, tcfg) -> None:
    """Every size and setting the files state is what the program runs."""
    c, t = cell.config, cell.traffic
    want = {
        "n_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
        "n_heads": c["num_attention_heads"],
        "n_kv_heads": c["num_key_value_heads"], "hd": c["head_dim"],
        "d_ff": c["intermediate_size"], "vocab": c["vocab_size"],
        "padded_vocab": c["embedding_rows"], "dtype": c["compute_dtype"],
        "param_dtype": c["param_dtype"],
        "tie_embeddings": c.get("tie_word_embeddings", False),
    }
    got = {k: getattr(cfg, k) for k in want}
    opt = dataclasses.asdict(tcfg.optimizer)
    want.update({f"optimizer.{k}": v for k, v in t["optimizer"].items()})
    got.update({f"optimizer.{k}": opt.get(k) for k in t["optimizer"]})
    want.update(microbatches=t["microbatches"], remat=t["remat"])
    got.update(microbatches=tcfg.microbatches, remat=tcfg.remat)
    bad = {k: (want[k], got[k]) for k in want if want[k] != got[k]}
    if bad:
        raise ConfigMismatch(f"file vs program (want, got): {bad}")


def _tree_shapes(tree) -> dict[str, tuple]:
    return {"/".join(str(getattr(p, "key", p)) for p in path): tuple(x.shape)
            for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


class TimedSource:
    """The program's data source, with the host time of every
    ``batch_at`` the loop makes; each call also starts a loop iteration,
    so the window's clock and phases are kept here."""

    def __init__(self, source, loop: "Loop"):
        self.source = source
        self.loop = loop
        self._main = threading.get_ident()

    def batch_at(self, step: int):
        if threading.get_ident() != self._main:      # the prefetch thread
            return self.source.batch_at(step)
        self.loop.begin(step)
        with jax.profiler.TraceAnnotation("data"):
            t0 = time.perf_counter()
            batch = self.source.batch_at(step)
            self.loop.data_s[step] = time.perf_counter() - t0
        if step < self.loop.checked:
            self.loop.fed.append({k: np.array(v) for k, v in batch.items()})
        return batch


class _NoCheckpoint:
    """The loop saves nothing in the window; after a failed step there is
    nothing to restore, and the run ends."""

    def restore(self):
        raise WindowClosed("a step failed")

    def wait(self):
        pass


@dataclasses.dataclass
class Loop:
    step_fn: object
    checked: int
    seconds: float
    trace_steps: int
    b1: float
    layout: dict
    key: object
    t0: float
    starts: dict = dataclasses.field(default_factory=dict)
    data_s: dict = dataclasses.field(default_factory=dict)
    step_s: dict = dataclasses.field(default_factory=dict)
    fed: list = dataclasses.field(default_factory=list)
    losses: list = dataclasses.field(default_factory=list)
    grad_norms: dict | None = None
    change_norms: dict | None = None
    setup_s: float | None = None
    window: tuple | None = None       # (first step, start time)
    window_end: float | None = None
    timed_steps: int = 0
    traced: list = dataclasses.field(default_factory=list)
    failed: int = 0
    trace_dir: Path | None = None

    def begin(self, step: int) -> None:
        now = time.perf_counter()
        if step == self.checked and self.window is None:
            self.setup_s = now - self.t0
            self.window = (step, now)
        elif self.window is not None and self.window_end is None \
                and now - self.window[1] >= self.seconds:
            self.window_end = max(self.starts[s] + self.step_s[s]
                                  for s in self.step_s if s >= self.window[0])
            self.timed_steps = step - self.window[0]
            if not self.trace_steps:
                raise WindowClosed
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
        elif self.window_end is not None \
                and len(self.traced) >= self.trace_steps:
            jax.profiler.stop_trace()
            raise WindowClosed
        self.starts[step] = now

    def __call__(self, state, batch, step):
        try:
            with jax.profiler.StepTraceAnnotation("train", step_num=step):
                with jax.profiler.TraceAnnotation("dispatch"):
                    jb = {k: jnp.asarray(v) for k, v in batch.items()}
                    state, metrics = self.step_fn(state, jb)
                with jax.profiler.TraceAnnotation("wait"):
                    jax.block_until_ready(metrics["loss"])
        except Exception:
            self.failed += 1
            raise
        self.step_s[step] = time.perf_counter() - self.starts[step]
        if self.window_end is not None:
            self.traced.append(step)
        if step < self.checked:
            self._read_checked(step, state, metrics)
        return state, metrics

    def _read_checked(self, step, state, metrics) -> None:
        self.losses.append(float(metrics["loss"]))
        if step == 0:
            norms = jax.jit(leaf_norms)(state["opt"]["mu"])
            self.grad_norms = jax.tree.map(
                lambda x: np.asarray(x) / (1.0 - self.b1),
                jax.device_get(norms))
        if step == self.checked - 1:
            layout = self.layout
            change = jax.jit(lambda p, k: leaf_norms(jax.tree.map(
                jnp.subtract, p, make_weights(layout, k))))
            self.change_norms = jax.device_get(
                change(state["params"], self.key))


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t0: float) -> dict:
    """One run of a training cell; returns the result's fields."""
    from repro.data import Prefetcher, make_source
    from repro.launch import train as launch
    from repro.models import zoo
    from repro.optim import init_opt_state
    from repro.sharding import named_sharding_tree
    from repro.train import run_with_recovery, state_specs

    c, t = cell.config, cell.traffic
    args = program_args(cell)
    cfg, tcfg, mesh, plan = launch.build(args)
    check_program_config(cell, cfg, tcfg)
    ref = reference.family(c["family"])
    layout = ref.layout(c)
    want = {p: tuple(s) for p, (s, _) in layout.items()}
    if want != _tree_shapes(zoo.abstract(cfg)):
        raise ConfigMismatch("the program's parameter tree differs from "
                             f"the {c['family']} layout")
    key = key_from_seed(seed)
    devices = list(mesh.devices.ravel())
    checked = t["checked_steps"]

    loop = Loop(step_fn=None, checked=checked, seconds=seconds,
                trace_steps=t["trace_steps"] if trace else 0,
                b1=t["optimizer"]["b1"], layout=layout, key=key, t0=t0,
                trace_dir=TRACE_DIR / cell.name)
    base = launch.data_source(args, cfg)
    source = TimedSource(make_source(dataclasses.replace(base.cfg,
                                                         seed=seed)), loop)
    prefetch = Prefetcher(source)
    try:
        with jax.set_mesh(mesh):
            shardings = (named_sharding_tree(plan, mesh,
                                             state_specs(cfg, tcfg))
                         if len(devices) > 1 else None)

            def make_state(k):
                params = make_weights(layout, k)
                return {"params": params,
                        "opt": init_opt_state(tcfg.optimizer, params)}

            state = jax.jit(make_state, out_shardings=shardings)(key)
            loop.step_fn = launch.jit_train_step(cfg, tcfg, plan)
            try:
                run_with_recovery(loop, state, n_steps=1 << 62,
                                  save_every=0, manager=_NoCheckpoint(),
                                  data_prefetch=prefetch)
            except WindowClosed:
                pass
            del state
    finally:
        prefetch.close()
    gc.collect()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

    record = _window_record(cell, loop, c, t, devices)
    if trace and loop.traced:
        files = sorted(loop.trace_dir.glob("**/*.xplane.pb"))
        record["trace"] = (trace_reduce.reduce(trace_reduce.load(
            str(files[-1]))) if files else None)
        shutil.rmtree(loop.trace_dir, ignore_errors=True)

    expected = [data.synthetic_lm(seed, s, c["vocab_size"], t["seq_len"],
                                  t["global_batch"]) for s in range(checked)]
    ref_out = reference.run(c, t["optimizer"], key, expected, devices)
    program = {"losses": loop.losses, "grad_norms": loop.grad_norms,
               "change_norms": loop.change_norms}
    values = check.numbers(program, ref_out, loop.fed, expected)
    ok, rows = check.judge(values, cell.limits)

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = (record["e2e"].get(m["name"]) if not trace
                 else metric_reader(m["name"])(record))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    out = {"correct": ok and loop.failed == 0 and loop.timed_steps > 0,
           "attempted": loop.timed_steps + (loop.failed > 0),
           "failed": loop.failed, "metrics": metrics, "device": device}
    tr = record.get("trace")
    if trace and tr:
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in rows}
    return out


def _window_record(cell: Cell, loop: Loop, c: dict, t: dict,
                   devices) -> dict:
    """What the window measured, for the end-to-end metrics and the
    per-layer readers."""
    if loop.window is None or loop.window_end is None:
        return {"e2e": {}, "trace": None}
    first = loop.window[0]
    steps = [s for s in loop.step_s if first <= s < first + loop.timed_steps]
    window_s = loop.window_end - loop.window[1]
    tokens = t["global_batch"] * t["seq_len"] * len(steps)
    step_ms = sorted(1e3 * loop.step_s[s] for s in steps)
    p90 = step_ms[max(0, math.ceil(0.9 * len(step_ms)) - 1)]
    tokens_per_s = tokens / window_s
    return {
        "e2e": {"train_tokens_per_s": tokens_per_s,
                "train_step_ms_p90": p90, "setup_s": loop.setup_s},
        "steps": len(steps), "window_s": window_s,
        "tokens_per_s": tokens_per_s,
        "data_s": [loop.data_s[s] for s in steps],
        "flops_per_token": work.train_flops_per_token(c, t["seq_len"]),
        "device_kind": devices[0].device_kind,
        "chips": len(devices),
        "trace": None,
    }
