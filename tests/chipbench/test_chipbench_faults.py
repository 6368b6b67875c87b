"""A run with the timed path broken underneath comes out not correct.

Each case skips the command's look for a chip and drives the rest of a
run, at a CPU size (each family's ``TINY``), through the harness's own
loop, feed and check:
once sound, once with a step that returns its state unchanged, and once
with a step that leaves half of the batch out and takes the mean over
the rest (on the (2, 2) mesh, leaving out the exchange between the data
shards drops the same half)."""

import time

import jax
import pytest

from chipbench import reference
from conftest import tiny_cell


def _broken(fault):
    from repro.train import make_train_step

    def factory(cfg, tcfg, plan):
        step = jax.jit(make_train_step(cfg, tcfg))

        def unchanged(state, batch):
            return state, step(state, batch)[1]

        def half_batch(state, batch):
            return step(state, {k: v[: v.shape[0] // 2]
                                for k, v in batch.items()})

        return {"unchanged": unchanged, "half_batch": half_batch}[fault]
    return factory


@pytest.mark.parametrize("family", reference.families())
@pytest.mark.parametrize("fault", [None, "unchanged", "half_batch"])
def test_fault_comes_out_not_correct(family, fault, monkeypatch):
    from chipbench import spec
    from repro.launch import train as launch
    if fault:
        monkeypatch.setattr(launch, "jit_train_step", _broken(fault))
    cell = tiny_cell(family)
    out = spec.kind_module("train").run(cell, 2**31 + 17, 0.3, False,
                                        time.perf_counter())
    assert out["correct"] is (fault is None), out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_tokens_per_s",
                                   "train_step_ms_p90", "setup_s"}


def test_traced_run_records_its_files_and_the_steps_scopes(monkeypatch):
    """A traced run hands the readers a record with the cell's files as
    run, and compiles the step afresh for HLO text whose operations carry
    the program's scopes.  (A CPU trace has no device plane, so there is
    no trace to reduce and no reader reads anything.)"""
    from chipbench import scope_reduce, spec
    kind = spec.kind_module("train")
    seen = {}

    def reader(name):
        def read(record):
            seen["record"] = record
        return read

    def trace_record(path, hlo):
        seen["hlo"] = hlo
        return real(path, hlo)

    real = scope_reduce.trace_record
    monkeypatch.setattr(kind, "metric_reader", reader)
    monkeypatch.setattr(scope_reduce, "trace_record", trace_record)
    cell = tiny_cell("dense")
    out = kind.run(cell, 2**31 + 19, 0.3, True, time.perf_counter())
    assert out["correct"] and out["metrics"] == {}, out["checks"]
    record = seen["record"]
    assert record["config"] == cell.config
    assert record["traffic"] == cell.traffic
    assert record["trace"] is None
    names = scope_reduce.hlo_op_names(seen["hlo"]).values()
    scopes = set().union(*map(scope_reduce.scope_names, names))
    assert {"embed", "attention", "mlp", "head", "optimizer"} <= scopes
