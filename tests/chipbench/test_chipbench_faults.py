"""A run with the timed path broken underneath comes out not correct.

Each case skips the command's look for a chip and drives the rest of a
run, at a CPU size, through the harness's own loop, feed and check:
once sound, once with a step that returns its state unchanged, and once
with a step that leaves half of the batch out and takes the mean over
the rest (on the (2, 2) mesh, leaving out the exchange between the data
shards drops the same half)."""

import time

import jax
import pytest

from conftest import tiny_cell

LIMITS = {"dense": "olmo-1b-8l.pretrain-2k",
          "rwkv6": "rwkv6-1.6b-cut.pretrain-2k"}


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


@pytest.mark.parametrize("family", ["dense", "rwkv6"])
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
