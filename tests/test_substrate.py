"""Substrate: optimizer, the train state and its partition, data
pipeline, checkpoint, fault tolerance, serving engine."""

import contextlib
import dataclasses
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import DataConfig, MmapTokens, Prefetcher, SyntheticLM
from repro.models.common import default_plan, partitioned, replicated_plan
from repro.optim import (AdamWConfig, adamw_update, clip_by_global_norm,
                         init_opt_state, schedule_lr)
from repro.serving import Request, ServeEngine
from repro.train import (CheckpointManager, Heartbeat, StragglerMonitor,
                         TrainConfig, abstract_state, init_state,
                         run_with_recovery, state_specs)


class TestOptimizer:
    def test_adamw_converges_quadratic(self):
        cfg = AdamWConfig(lr=0.1, weight_decay=0.0, total_steps=200,
                          warmup_steps=1, schedule="constant")
        target = jnp.asarray([1.0, -2.0, 3.0])
        params = {"w": jnp.zeros(3)}
        st = init_opt_state(cfg, params)
        for _ in range(200):
            g = {"w": 2 * (params["w"] - target)}
            params, st, _ = adamw_update(cfg, g, st, params)
        assert float(jnp.abs(params["w"] - target).max()) < 0.05

    def test_clip(self):
        g = {"a": jnp.full((4,), 10.0)}
        clipped, norm = clip_by_global_norm(g, 1.0)
        assert float(norm) == pytest.approx(20.0)
        import math
        assert float(jnp.linalg.norm(clipped["a"])) == pytest.approx(1.0,
                                                                     rel=1e-5)

    def test_schedule_shapes(self):
        cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                          min_lr_ratio=0.1)
        lr0 = float(schedule_lr(cfg, jnp.asarray(0)))
        lr_peak = float(schedule_lr(cfg, jnp.asarray(10)))
        lr_end = float(schedule_lr(cfg, jnp.asarray(100)))
        assert lr0 < lr_peak
        assert lr_end == pytest.approx(0.1, rel=1e-3)

    def test_bf16_moments(self):
        cfg = AdamWConfig(moment_dtype="bfloat16")
        st = init_opt_state(cfg, {"w": jnp.zeros((4,))})
        assert st["mu"]["w"].dtype == jnp.bfloat16


# the three plans the code builds: one chip's, the launcher's (2,2) mesh,
# and a pod-crossing train cell of the dry-run (plan_for_shape)
PLANS = {
    "replicated": replicated_plan(),
    "default": default_plan(),
    "pod_seq": dataclasses.replace(default_plan(), seq_axes=("model",),
                                   batch_axes=("pod", "data")),
}


@pytest.mark.parametrize("name", list(PLANS))
def test_partition_comes_from_the_plan(name):
    plan = PLANS[name]
    cfg = get_config("olmo-1b", smoke=True)
    out = partitioned(cfg, plan)
    assert out.batch_axes == tuple(plan.batch_axes)
    assert out.seq_axes == tuple(plan.seq_axes)
    assert out.head_axes == tuple(plan.rules["heads"])
    assert out.ff_axes == tuple(plan.rules["ff"])
    rest = [f.name for f in dataclasses.fields(cfg)
            if f.name not in ("batch_axes", "seq_axes", "head_axes",
                              "ff_axes")]
    assert [getattr(out, k) for k in rest] == [getattr(cfg, k) for k in rest]


class TestTpBoundaryDispatch:
    """``models.layers.tp_boundary_path`` runs a block's tensor-parallel
    boundaries as collective matmuls only where the partition gives them
    something to do: a dense block whose heads and MLP are split over the
    same axes of more than one device, dividing the sequence and heads."""

    # olmo-1b-16l.pretrain-2k-mesh2x2: default_plan on the (2, 2) mesh
    MESH2X2 = {"data": 2, "model": 2}

    @pytest.mark.parametrize("arch,plan,seq,mesh,path", [
        ("olmo-1b", "default", 2048, MESH2X2, "collective"),
        ("olmo-1b", "replicated", 2048, {"data": 1}, "gspmd"),  # one chip
        ("olmo-1b", "default", 2048, {}, "gspmd"),              # no mesh
        ("mixtral-8x7b", "default", 2048, MESH2X2, "gspmd"),    # MoE
        ("olmo-1b", "pod_seq", 2048, {"pod": 2, **MESH2X2}, "gspmd"),
        ("olmo-1b", "default", 2047, MESH2X2, "gspmd"),         # S % model
        ("olmo-1b", "default", 2048, {"data": 4, "model": 1}, "gspmd"),
        # 8 kv heads over a 16-way model axis: the dry-run's llama3-405b
        ("llama3-405b", "default", 4096, {"data": 16, "model": 16},
         "gspmd"),
    ])
    def test_path(self, arch, plan, seq, mesh, path):
        from repro.models.layers import tp_boundary_path
        cfg = partitioned(get_config(arch), PLANS[plan])
        assert tp_boundary_path(cfg, seq, mesh) == path

    def test_counter_sees_the_collective_path(self):
        """Traced under the (data 2, model 2) mesh with ``default_plan``'s
        partition, a dense olmo forward counts one collective step and no
        GSPMD one; the same forward unpartitioned counts one GSPMD step."""
        from repro.models import zoo
        from repro.obs import tp_paths
        mesh = jax.sharding.AbstractMesh(
            (2, 2), ("data", "model"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2)
        tok = jax.ShapeDtypeStruct((2, 128), jnp.int32)
        smoke = get_config("olmo-1b", smoke=True)
        for cfg, ctx, path in [
                (partitioned(smoke, default_plan()),
                 jax.sharding.use_abstract_mesh(mesh), "collective"),
                (smoke, contextlib.nullcontext(), "gspmd")]:
            before = tp_paths().snapshot()
            with ctx:
                jax.eval_shape(lambda p, t: zoo.loss_fn(
                    cfg, p, {"tokens": t, "targets": t}),
                    zoo.abstract(cfg), tok)
            after = tp_paths().snapshot()
            other = "gspmd" if path == "collective" else "collective"
            assert after.get(path, 0) == before.get(path, 0) + 1
            assert after.get(other, 0) == before.get(other, 0)


@pytest.mark.parametrize("arch", ["olmo-1b", "mixtral-8x7b"])
def test_state_trees_agree(arch):
    """The state, its abstract form and its logical specs are one tree,
    with nothing beside the params and the optimizer's moments."""
    cfg = get_config(arch, smoke=True)
    tcfg = TrainConfig()
    state = init_state(cfg, tcfg, jax.random.PRNGKey(0))
    specs = state_specs(cfg, tcfg)
    assert set(state) == set(specs) == {"params", "opt"}
    tree = jax.tree.structure(state)
    assert jax.tree.structure(abstract_state(cfg, tcfg)) == tree
    assert jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, tuple)) == tree


class TestData:
    def test_synthetic_deterministic_across_hosts(self):
        cfg = DataConfig(seq_len=32, global_batch=8, vocab=101, seed=7)
        whole = SyntheticLM(cfg).batch_at(3)
        parts = [SyntheticLM(cfg, host_id=h, num_hosts=4).batch_at(3)
                 for h in range(4)]
        # every host's rows appear in its own slice deterministically
        for h, p in enumerate(parts):
            assert p["tokens"].shape == (2, 32)
            again = SyntheticLM(cfg, host_id=h, num_hosts=4).batch_at(3)
            np.testing.assert_array_equal(p["tokens"], again["tokens"])

    def test_targets_shifted(self):
        cfg = DataConfig(seq_len=16, global_batch=2, vocab=50, seed=0)
        b = SyntheticLM(cfg).batch_at(0)
        assert b["tokens"].shape == b["targets"].shape

    def test_prefetcher_resume_cursor(self):
        cfg = DataConfig(seq_len=8, global_batch=2, vocab=11, seed=1)
        src = SyntheticLM(cfg)
        pf = Prefetcher(src, start_step=5)
        b5 = pf.next()
        assert pf.state()["cursor"] == 6
        np.testing.assert_array_equal(b5["tokens"], src.batch_at(5)["tokens"])
        pf.close()

    def test_mmap_loader(self):
        with tempfile.NamedTemporaryFile(suffix=".bin", delete=False) as f:
            arr = np.arange(10000, dtype=np.uint16) % 997
            arr.tofile(f.name)
            path = f.name
        cfg = DataConfig(seq_len=64, global_batch=4, vocab=997, seed=0,
                         kind="mmap", path=path)
        src = MmapTokens(cfg)
        b0 = src.batch_at(0)
        b0_again = src.batch_at(0)
        np.testing.assert_array_equal(b0["tokens"], b0_again["tokens"])
        assert b0["tokens"].shape == (4, 64)
        os.unlink(path)


class TestCheckpoint:
    def test_roundtrip_and_gc(self):
        state = {"params": {"w": jnp.arange(6.0).reshape(2, 3)},
                 "opt": {"step": jnp.asarray(3)}}
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, keep_last_k=2)
            for s in (1, 2, 3):
                mgr.save(s, state, extra={"cursor": s}, block=True)
            dirs = [x for x in os.listdir(d) if x.startswith("step_")]
            assert len(dirs) == 2                      # gc kept last 2
            restored, extra, step = mgr.restore()
            assert step == 3 and extra["cursor"] == 3
            np.testing.assert_array_equal(
                np.asarray(restored["params"]["w"]),
                np.asarray(state["params"]["w"]))

    def test_restore_specific_step(self):
        state = {"w": jnp.ones(3)}
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, keep_last_k=5)
            mgr.save(1, {"w": jnp.ones(3)}, block=True)
            mgr.save(2, {"w": 2 * jnp.ones(3)}, block=True)
            r1, _, _ = mgr.restore(step=1)
            assert float(r1["w"][0]) == 1.0

    def test_latest_pointer_atomic(self):
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d)
            assert mgr.latest_step() is None
            mgr.save(7, {"w": jnp.zeros(1)}, block=True)
            assert mgr.latest_step() == 7


class TestFault:
    def test_straggler_excluded(self):
        mon = StragglerMonitor(min_observations=2, consecutive_to_exclude=2)
        for _ in range(4):
            mon.observe({"h0": 1.0, "h1": 1.02, "h2": 0.99, "h3": 6.0})
        assert mon.healthy_hosts(["h0", "h1", "h2", "h3"]) == \
            ["h0", "h1", "h2"]

    def test_transient_slowness_recovers(self):
        mon = StragglerMonitor(min_observations=1, consecutive_to_exclude=3)
        mon.observe({"h0": 1.0, "h1": 1.0, "h2": 1.0, "h3": 8.0})
        mon.observe({"h0": 1.0, "h1": 1.0, "h2": 1.0, "h3": 1.0})
        for _ in range(8):
            mon.observe({"h0": 1.0, "h1": 1.0, "h2": 1.0, "h3": 1.01})
        assert "h3" in mon.healthy_hosts(["h0", "h1", "h2", "h3"])

    def test_heartbeat_staleness(self):
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "hb.json")
            hb = Heartbeat(path, interval_s=0.0)
            assert Heartbeat.is_stale(path, 1.0)
            hb.beat(1, force=True)
            assert not Heartbeat.is_stale(path, 10.0)

    def test_recovery_replays_from_checkpoint(self):
        from repro.data import DataConfig, SyntheticLM, Prefetcher
        cfg = DataConfig(seq_len=4, global_batch=2, vocab=7, seed=0)
        pf = Prefetcher(SyntheticLM(cfg))
        calls = {"n": 0}

        def step_fn(state, batch, step):
            calls["n"] += 1
            if calls["n"] == 8:
                raise RuntimeError("injected")
            return {"n": state["n"] + 1}, {"loss": 0.0}

        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d)
            state, stats = run_with_recovery(
                step_fn, {"n": jnp.asarray(0)}, n_steps=10, save_every=3,
                manager=mgr, data_prefetch=pf)
        pf.close()
        assert stats.failures == 1 and stats.restores == 1
        # replayed steps re-execute: total applied increments = 10 + replays
        assert int(state["n"]) == 10 + stats.steps_replayed \
            or int(state["n"]) == 10

    @pytest.mark.parametrize("exc", [
        jax.errors.JaxRuntimeError(
            "RESOURCE_EXHAUSTED: Error allocating device buffer"),
        jax.errors.JaxRuntimeError(
            "INVALID_ARGUMENT: the backend refused the program"),
        ValueError("block shape not divisible by (8, 128)"),
    ], ids=["hbm_oom", "compile_refused", "lowering_error"])
    def test_recovery_reraises_what_replay_cannot_fix(self, exc):
        calls = {"n": 0}

        def step_fn(state, batch, step):
            calls["n"] += 1
            raise exc

        with tempfile.TemporaryDirectory() as d:
            with pytest.raises(type(exc)):
                run_with_recovery(step_fn, {}, n_steps=3, save_every=1,
                                  manager=CheckpointManager(d))
        assert calls["n"] == 1


def test_train_launcher_runs_and_reports_every_loss():
    from repro.launch.train import parser, train
    with tempfile.TemporaryDirectory() as d:
        args = parser().parse_args([
            "--smoke", "--layers", "1", "--steps", "3", "--batch", "2",
            "--seq", "16", "--save-every", "0", "--ckpt-dir", d])
        res = train(args)
        assert CheckpointManager(d).latest_step() == 3
    assert res["layers"] == 1 and res["failures"] == 0
    assert len(res["losses"]) == 3
    assert all(np.isfinite(res["losses"]))


def test_compile_cache_placement(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; without it the cache goes
    to the checkout's fixed .jax_cache."""
    from repro.launch.compile_cache import REPO_CACHE_DIR, place_compile_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert place_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert place_compile_cache() == str(REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
        assert (REPO_CACHE_DIR.parent / "pyproject.toml").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        jax.config.update("jax_hlo_source_file_canonicalization_regex", None)


def test_compiled_source_files_are_named_from_the_checkout():
    """After placing the cache, a lowered program names its source files
    relative to the checkout: the Mosaic module of a Pallas kernel keeps
    them, and it is part of the persistent cache's key."""
    from repro.launch.compile_cache import REPO_ROOT, place_compile_cache
    try:
        place_compile_cache()
        text = jax.jit(lambda x: x * 2).lower(1.0).as_text(debug_info=True)
    finally:
        jax.config.update("jax_hlo_source_file_canonicalization_regex", None)
    assert '"tests/test_substrate.py"' in text
    assert str(REPO_ROOT) not in text


class TestServing:
    def _engine(self, n_slots=3):
        from repro.configs import get_config
        from repro.models import zoo
        cfg = get_config("olmo-1b", smoke=True)
        params = zoo.init(cfg, jax.random.PRNGKey(0))
        return cfg, ServeEngine(cfg, params, n_slots=n_slots, max_len=64)

    def test_all_requests_served(self):
        cfg, eng = self._engine()
        rng = np.random.default_rng(0)
        for i in range(7):
            eng.submit(Request(
                rid=i, prompt=rng.integers(0, cfg.vocab, 6).astype(np.int32),
                max_new_tokens=5))
        done = eng.run()
        assert len(done) == 7
        assert all(1 <= len(r.output) <= 5 for r in done)
        assert len(eng.stats) == 3                     # ceil(7/3) waves

    def test_eos_stops_generation(self):
        cfg, eng = self._engine(n_slots=1)
        prompt = np.asarray([1, 2, 3], np.int32)
        # pick eos = the model's actual first greedy token
        from repro.models import zoo
        probe_eng = eng
        probe_eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=4))
        first = probe_eng.run()[0].output[0]
        cfg2, eng2 = self._engine(n_slots=1)
        eng2.submit(Request(rid=1, prompt=prompt, max_new_tokens=16,
                            eos_id=first))
        done = eng2.run()
        assert done[0].output[-1] == first and len(done[0].output) <= 16

    def test_utilization_reported(self):
        cfg, eng = self._engine()
        rng = np.random.default_rng(1)
        for i in range(3):
            eng.submit(Request(
                rid=i, prompt=rng.integers(0, cfg.vocab, 4).astype(np.int32),
                max_new_tokens=3 + i))
        eng.run()
        assert 0.0 < eng.mean_slot_utilization <= 1.0

    def test_mixed_length_prefill_matches_unpadded_run(self):
        """The wave prefill left-pads, and the models' causal attention
        has no pad mask — so a shorter request's first generated token
        must come from the per-length exact prefill, identical to running
        that request alone, unpadded."""
        cfg, _ = self._engine()
        rng = np.random.default_rng(7)
        prompts = [rng.integers(1, cfg.vocab, n).astype(np.int32)
                   for n in (3, 6, 10)]
        solo_tokens = []
        for p in prompts:
            _, solo = self._engine(n_slots=1)
            solo.submit(Request(rid=0, prompt=p.copy(), max_new_tokens=1))
            solo_tokens.append(solo.run()[0].output[0])
        _, eng = self._engine(n_slots=3)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p.copy(), max_new_tokens=1))
        done = {r.rid: r for r in eng.run()}
        for i, tok in enumerate(solo_tokens):
            assert done[i].output == [tok], \
                f"prompt {i} (len {len(prompts[i])}) diverged from solo run"

    def test_zero_max_new_tokens_gets_zero_tokens(self):
        cfg, eng = self._engine(n_slots=2)
        rng = np.random.default_rng(2)
        eng.submit(Request(rid=0,
                           prompt=rng.integers(1, cfg.vocab, 5).astype(
                               np.int32),
                           max_new_tokens=0))
        eng.submit(Request(rid=1,
                           prompt=rng.integers(1, cfg.vocab, 8).astype(
                               np.int32),
                           max_new_tokens=3))
        done = {r.rid: r for r in eng.run()}
        assert done[0].output == []            # asked for 0, got 0
        assert len(done[1].output) == 3
        # useful_tokens must not count the suppressed prefill token
        assert eng.stats[0].useful_tokens == 3

    def test_all_zero_wave_spends_no_slot_capacity(self):
        cfg, eng = self._engine(n_slots=2)
        for i in range(2):
            eng.submit(Request(rid=i,
                               prompt=np.arange(1, 5, dtype=np.int32),
                               max_new_tokens=0))
        done = eng.run()
        assert all(r.output == [] for r in done)
        assert eng.stats[0].decode_steps == 0
        assert eng.stats[0].slot_token_capacity == 0
        assert eng.stats[0].useful_tokens == 0
