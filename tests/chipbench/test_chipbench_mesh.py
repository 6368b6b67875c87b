"""The sharded training step of the four-chip cell, at a CPU size.

``olmo-1b-16l.pretrain-2k-mesh2x2`` trains through the launcher's (data 2,
model 2) mesh and ``default_plan``: FSDP over ``data`` on the embed
dimension, tensor parallelism over ``model`` on heads, kv, ff and vocab.
Here the same path runs on four forced CPU devices, in a subprocess (the
test process keeps its one device), with a two-layer model at a sixteenth
of OLMo-1B's width in float32:

* the program's step, from ``launch.build``, against the plain float32
  reference (``chipbench/reference``) on the same seeded weights and rows:
  each step's loss, the first step's clipped gradient and the parameters'
  change over three steps, leaf by leaf; and the step compiles once;
* the harness's own run on that mesh, held to the four-chip cell's
  limits: sound, it comes out correct; with a step that returns its state
  unchanged, or one that trains on half of the batch, it does not.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CELL = "olmo-1b-16l.pretrain-2k-mesh2x2"
SEED = 2**31 + 29

# Tolerances of the float32 comparison.  Program and reference differ in
# the order of every reduction (partial sums per shard, then across them),
# in the attention's form (the program's chunked jnp attention against a
# plain softmax) and in how LayerNorm, RoPE and the cross-entropy are
# written; in float32 each of these rounds at about 6e-8 of a value.
LOSS_RTOL = 1e-5     # a mean over 512 positions: measured ~1e-7
GRAD_RTOL = 1e-4     # a leaf's norm over the larger of its reference norm
                     # and the median leaf's: measured ~3e-7
CHANGE_RTOL = 1e-4   # three AdamW updates, m / sqrt(v) near +-lr for every
                     # element, so rounding of the gradient moves them
                     # little: measured ~2e-6
# Each is at least ten times below what the float8 control reads at this
# size (test_chipbench_control: loss 1e-4, gradients and change 1e-2).

CODE = """
import dataclasses, json, sys, time
import jax, jax.numpy as jnp, numpy as np
from chipbench import check, data, reference, spec
from chipbench.reference.common import key_from_seed, leaf_norms, make_weights
from conftest import tiny_cell
from repro.launch import train as launch
from repro.models.common import default_plan
from repro.obs import compile_counter
from repro.optim import init_opt_state
from repro.sharding import named_sharding_tree
from repro.train import make_train_step, state_specs

SEED, CELL = {seed}, {cell!r}
cell = dataclasses.replace(tiny_cell("dense", limits_of=CELL, batch=8),
                           chips=4)
kind = spec.kind_module("train")
c, t = cell.config, cell.traffic
out = {{}}

# the program's sharded step against the reference
cfg, tcfg, mesh, plan = launch.build(kind.program_args(cell))
kind.check_program_config(cell, cfg, tcfg)
out["mesh"] = dict(mesh.shape)
out["default_plan"] = plan.rules == default_plan().rules
layout = reference.family("dense").layout(c)
key = key_from_seed(SEED)
rows = [data.synthetic_lm(SEED, s, c["vocab_size"], t["seq_len"],
                          t["global_batch"]) for s in range(3)]
with jax.set_mesh(mesh):
    sh = named_sharding_tree(plan, mesh, state_specs(cfg, tcfg))

    def make_state(k):
        params = make_weights(layout, k)
        return {{"params": params,
                 "opt": init_opt_state(tcfg.optimizer, params)}}

    state = jax.jit(make_state, out_shardings=sh)(key)
    step = launch.jit_train_step(cfg, tcfg, plan)
    losses = []
    for s, batch in enumerate(rows):
        state, m = step(state, {{k: jnp.asarray(v) for k, v in batch.items()}})
        losses.append(float(m["loss"]))
        if s == 0:
            grads = jax.tree.map(
                lambda x: np.asarray(x) / (1 - t["optimizer"]["b1"]),
                jax.device_get(jax.jit(leaf_norms)(state["opt"]["mu"])))
            traces = compile_counter().read()[0]
    out["retraces"] = compile_counter().read()[0] - traces
    change = jax.device_get(jax.jit(lambda p: leaf_norms(jax.tree.map(
        jnp.subtract, p, make_weights(layout, key))))(state["params"]))
    out["sharded_params"] = sum(
        not x.sharding.is_fully_replicated
        for x in jax.tree.leaves(state["params"]))
del state
ref = reference.run(c, t["optimizer"], key, rows, jax.devices())
out["losses"], out["ref_losses"] = losses, ref["losses"]
out["grad_gap"] = check.worst_gap(grads, ref["grad_norms"])
out["change_gap"] = check.worst_gap(
    change, ref["change_norms"], check.moved_leaves(ref["grad_norms"]))

# the harness on the same mesh, sound and with a planted fault
real = launch.jit_train_step

def broken(fault):
    def factory(cfg, tcfg, plan):
        step = real(cfg, tcfg, plan)
        kept = jax.jit(make_train_step(           # the state not donated
            cfg, tcfg, batch_axes=tuple(plan.batch_axes)))

        def unchanged(state, batch):
            return state, kept(state, batch)[1]

        def half_batch(state, batch):
            return step(state, {{k: v[: v.shape[0] // 2]
                                 for k, v in batch.items()}})

        return {{"unchanged": unchanged, "half_batch": half_batch}}[fault]
    return factory

for fault in (None, "unchanged", "half_batch"):
    launch.jit_train_step = broken(fault) if fault else real
    run = kind.run(cell, SEED, 0.3, False, time.perf_counter())
    out[str(fault)] = {{"correct": run["correct"],
                        "attempted": run["attempted"],
                        "failed": run["failed"], "checks": run["checks"]}}
launch.jit_train_step = real
print("RESULT", json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_run():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT), str(ROOT / "src"), str(ROOT / "tests" / "chipbench")])
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(CODE.format(seed=SEED,
                                                           cell=CELL))],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT")]
    return json.loads(line[-1][len("RESULT "):])


def test_sharded_step_matches_reference(mesh_run):
    assert mesh_run["mesh"] == {"data": 2, "model": 2}
    assert mesh_run["default_plan"]
    assert mesh_run["sharded_params"] > 0
    # the state comes back from the step with the shardings it went in
    # with, so the step compiles once
    assert mesh_run["retraces"] == 0
    for got, want in zip(mesh_run["losses"], mesh_run["ref_losses"],
                         strict=True):
        assert abs(got - want) <= LOSS_RTOL * abs(want), (got, want)
    assert mesh_run["grad_gap"] <= GRAD_RTOL
    assert mesh_run["change_gap"] <= CHANGE_RTOL


@pytest.mark.parametrize("fault", [None, "unchanged", "half_batch"])
def test_mesh_fault_comes_out_not_correct(mesh_run, fault):
    run = mesh_run[str(fault)]
    assert run["correct"] is (fault is None), run["checks"]
    assert run["attempted"] > 0 and run["failed"] == 0


def test_control_fails_the_cell_limits():
    """At ``test_chipbench_control``'s size, the float8 control fails the
    four-chip cell's limits, and the reference against itself passes."""
    import jax

    from chipbench import check, data, reference
    from chipbench.reference.common import key_from_seed
    from test_chipbench_control import SMALL, _limits
    c = SMALL
    opt = json.loads((ROOT / "chipbench" / "traffic" /
                      "pretrain-2k-mesh2x2.json").read_text())["optimizer"]
    rows = [data.synthetic_lm(SEED, s, c["vocab_size"], 128, 4)
            for s in range(3)]
    key, devices = key_from_seed(SEED), jax.devices()[:1]
    ref = reference.run(c, opt, key, rows, devices)
    again = reference.run(c, opt, key, rows, devices)
    ctl = reference.run(c, opt, key, rows, devices, control=True)
    limits = _limits(CELL)
    assert check.judge(check.numbers(again, ref, rows, rows), limits)[0]
    ok, table = check.judge(check.numbers(ctl, ref, rows, rows), limits)
    assert not ok, table
