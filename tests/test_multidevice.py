"""Multi-device tests run in a SUBPROCESS with forced host devices, so the
main pytest process keeps seeing exactly 1 device (task-spec requirement:
smoke tests and benches see 1 device)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# each subprocess finishes in well under a minute here; the cap keeps a
# hung child from eating the whole suite's time limit
def run_py(code: str, devices: int = 8, timeout: int = 180) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# the flash kernel per (batch, heads) shard of a (data 2, model 2) mesh,
# through ``models.layers.attention`` steered onto its kernel path (the
# kernel in interpret mode, blocks of 128), against the float32 oracle:
# (name, kv heads, window); q is (4, 256, 4, 64)
FLASH_CASES = [("causal_mha", 4, None), ("causal_gqa", 2, None),
               ("sliding_window", 4, 64)]


@pytest.fixture(scope="module")
def flash_per_shard_errors() -> dict:
    out = run_py(f"""
        import json
        from functools import partial
        import jax, jax.numpy as jnp
        from repro.kernels.flash_attention.ops import flash_attention
        from repro.kernels.flash_attention.ref import attention_ref
        from repro.launch.mesh import make_mesh
        from repro.models import layers
        from repro.obs import attention_paths

        layers._platform = lambda: "tpu"
        layers.flash_attention = partial(flash_attention, block_q=128,
                                         block_k=128, interpret=True)
        mesh = make_mesh((2, 2), ("data", "model"))

        def rel(a, b):
            return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))

        errors = {{}}
        for name, kv, window in {FLASH_CASES!r}:
            ks = jax.random.split(jax.random.PRNGKey(kv), 4)
            q = jax.random.normal(ks[0], (4, 256, 4, 64))
            k = jax.random.normal(ks[1], (4, 256, kv, 64))
            v = jax.random.normal(ks[2], (4, 256, kv, 64))
            w = jax.random.normal(ks[3], (4, 256, 4, 64))

            def sharded(q, k, v):
                return layers.attention(q, k, v, window=window,
                                        batch_axes=("data",),
                                        head_axes=("model",))

            def ref(q, k, v):
                return attention_ref(q, k, v, window=window)

            def loss(f):
                return lambda q, k, v: jnp.sum(f(q, k, v) * w)

            before = attention_paths().snapshot()
            with jax.set_mesh(mesh):
                o = jax.jit(sharded)(q, k, v)
                g = jax.jit(jax.grad(loss(sharded), (0, 1, 2)))(q, k, v)
            after = attention_paths().snapshot()
            g_ref = jax.grad(loss(ref), (0, 1, 2))(q, k, v)
            errors[name] = {{
                "flash_calls": after.get("flash", 0) - before.get("flash", 0),
                "dense_calls": after.get("dense", 0) - before.get("dense", 0),
                "o": rel(o, ref(q, k, v)),
                **{{d: rel(a, b) for d, a, b in zip(("dq", "dk", "dv"),
                                                   g, g_ref)}}}}
        print(json.dumps(errors))
    """, devices=4)
    return json.loads(out.strip().splitlines()[-1])


@pytest.mark.parametrize("name", [c[0] for c in FLASH_CASES])
def test_flash_attention_per_shard_matches_reference(flash_per_shard_errors,
                                                     name):
    """Output and q/k/v gradients at float32 rounding (measured
    ~1e-6 of the largest element), with no jnp attention traced."""
    got = flash_per_shard_errors[name]
    assert got["flash_calls"] == 2 and got["dense_calls"] == 0, got
    for part in ("o", "dq", "dk", "dv"):
        assert got[part] < 1e-5, (part, got)


def test_main_process_single_device():
    import jax
    assert jax.device_count() == 1


# the collective matmuls of ``repro.sharding.collective_matmul`` against
# the einsums GSPMD partitions (its all-reduce or reduce-scatter sums the
# same partial products), with the same shardings, on four forced CPU
# devices: (name, (data, model) mesh, dtype, largest error allowed, as a
# share of the reference's largest element).  f32 measured up to 4.8e-7,
# bf16 up to 7.3e-3: the two sum their bf16 partial products over the
# model axis in other orders and chunks (2**-8 is one rounding); against
# the float32 result each errs by up to 8.9e-3.
CM_CASES = [("model2_f32", (2, 2), "float32", 1e-5),
            ("model2_bf16", (2, 2), "bfloat16", 2e-2),
            ("model4_f32", (1, 4), "float32", 1e-5),
            ("model4_bf16", (1, 4), "bfloat16", 2e-2)]


@pytest.fixture(scope="module")
def collective_matmul_errors() -> dict:
    out = run_py(f"""
        import json
        from functools import partial
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.sharding import ag_matmul, ag_matmul_rs, matmul_rs

        def swiglu(g, u):
            return jax.nn.silu(g) * u

        def ein(spec):
            return lambda a, b: jnp.einsum(spec, a, b)

        col, row = ein("bsd,dn->bsn"), ein("bsk,kd->bsd")
        # each primitive, the einsums it stands for, and the arguments it
        # takes: of the sequence-sharded x, the two column-parallel
        # weights, the row-parallel one and a head-sharded y
        PRIMS = {{
            "ag_matmul": (
                (0, 1, 2),
                lambda x, wa, wb: ag_matmul(x, (wa, wb), axes=("model",)),
                lambda x, wa, wb: (col(x, wa), col(x, wb))),
            "matmul_rs": (
                (4, 3),
                lambda y, wd: matmul_rs(y, wd, axes=("model",)),
                row),
            "ag_matmul_rs": (
                (0, 1, 2, 3),
                lambda x, wa, wb, wd: ag_matmul_rs(x, (wa, wb), wd, swiglu,
                                                   axes=("model",)),
                lambda x, wa, wb, wd: row(swiglu(col(x, wa), col(x, wb)),
                                          wd)),
        }}

        def rel(a, b):
            a, b = (jnp.asarray(v, jnp.float32) for v in (a, b))
            return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))

        errors = {{}}
        for name, shape, dtype, _ in {CM_CASES!r}:
            mesh = make_mesh(shape, ("data", "model"))
            ks = jax.random.split(jax.random.PRNGKey(len(errors)), 6)
            args = [jax.random.normal(k, s, jnp.float32).astype(dtype) / 4
                    for k, s in zip(ks, [(4, 32, 16), (16, 24), (16, 24),
                                         (24, 16), (4, 32, 24)])]
            specs = [P("data", "model"), P(None, "model"), P(None, "model"),
                     P("model"), P("data", None, "model")]
            errors[name] = {{}}
            with jax.set_mesh(mesh):
                args = [jax.device_put(a, NamedSharding(mesh, s))
                        for a, s in zip(args, specs)]
                for prim, (take, fn, ref) in PRIMS.items():
                    xs = [args[i] for i in take]

                    @partial(jax.jit, static_argnums=0)
                    def value_and_vjp(f, *xs):
                        out, vjp = jax.vjp(f, *xs)
                        ct = jax.tree.map(lambda o: jax.random.normal(
                            ks[5], o.shape, jnp.float32).astype(o.dtype), out)
                        return out, vjp(ct)

                    got = jax.tree.leaves(value_and_vjp(fn, *xs))
                    want = jax.tree.leaves(value_and_vjp(ref, *xs))
                    errors[name][prim] = [rel(a, b) for a, b in zip(got, want)]
        print(json.dumps(errors))
    """, devices=4)
    return json.loads(out.strip().splitlines()[-1])


@pytest.mark.parametrize("name,tol", [(c[0], c[3]) for c in CM_CASES])
def test_collective_matmuls_match_einsum(collective_matmul_errors, name,
                                         tol):
    """Each primitive's values and its gradients with respect to its input
    and its weights match the einsums', on a model axis of 2 and of 4."""
    for prim, errs in collective_matmul_errors[name].items():
        assert len(errs) >= 3 and max(errs) < tol, (prim, errs)


def test_chunk_dots_weight_gradient_rounds_once():
    """A weight's gradient over sequence chunks is summed in float32 and
    rounded to bfloat16 once, as one dot over the whole sequence rounds it:
    on integers whose float32 sums are exact, bit for bit the same (adding
    the chunks' rounded bf16 products would round twice)."""
    import jax
    import jax.numpy as jnp
    from repro.sharding.collective_matmul import _chunk_dots
    ks = jax.random.split(jax.random.PRNGKey(0), 3)

    def ints(k, shape):
        return jax.random.randint(k, shape, 1, 9).astype(jnp.bfloat16)

    rows = ints(ks[0], (2, 16, 4))
    w, ct = ints(ks[1], (4, 3)), ints(ks[2], (2, 16, 3))
    chunks = (rows[:, :8], rows[:, 8:])
    _, vjp = jax.vjp(_chunk_dots, chunks, w)
    _, dw = vjp((ct[:, :8], ct[:, 8:]))
    want = jnp.einsum("bsk,bsn->kn", rows, ct,
                      preferred_element_type=jnp.float32
                      ).astype(jnp.bfloat16)
    assert dw.dtype == jnp.bfloat16
    assert bool(jnp.any(jnp.abs(want.astype(jnp.float32)) > 256))
    assert bool(jnp.array_equal(dw, want)), (dw, want)


@pytest.mark.parametrize("prim", ["ag_matmul", "matmul_rs"])
def test_collective_matmul_puts_a_permute_between_dots(prim):
    """In the compiled program a collective-permute sits between two dots:
    one dot feeds it or is fed by it, and another does not depend on it,
    so the transfer can run under that dot."""
    code = f"""
        import re
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.sharding import ag_matmul, matmul_rs

        mesh = make_mesh((2, 2), ("data", "model"))
        def arg(shape, spec):
            return jax.ShapeDtypeStruct(shape, jnp.float32,
                                        sharding=NamedSharding(mesh, spec))
        with jax.set_mesh(mesh):
            if {prim!r} == "ag_matmul":
                fn = lambda x, w: ag_matmul(x, (w,), axes=("model",))[0]
                shapes = (arg((4, 16, 8), P("data", "model")),
                          arg((8, 12), P(None, "model")))
            else:
                fn = lambda y, w: matmul_rs(y, w, axes=("model",))
                shapes = (arg((4, 16, 12), P("data", None, "model")),
                          arg((12, 8), P("model")))
            text = jax.jit(fn).lower(*shapes).compile().as_text()
        entry = text[text.index("ENTRY"):].split("\\n}}")[0]
        operands = {{}}
        for line in entry.splitlines():
            m = re.match(r"\\s*(?:ROOT )?%(\\S+) = .*? ([a-z-]+)\\((.*)",
                         line)
            if m:
                operands[m.group(1)] = (m.group(2),
                                        set(re.findall(r"%([\\w.\\-]+)",
                                                       m.group(3))))

        def ancestors(name, seen=None):
            seen = set() if seen is None else seen
            for o in operands.get(name, ("", ()))[1]:
                if o not in seen:
                    seen.add(o)
                    ancestors(o, seen)
            return seen

        permutes = [n for n, (op, _) in operands.items()
                    if op.startswith("collective-permute")]
        dots = [n for n, (op, _) in operands.items() if op == "dot"]
        assert permutes and len(dots) >= 2, entry
        for p in permutes:
            around = [d for d in dots
                      if p in ancestors(d) or d in ancestors(p)]
            free = [d for d in dots
                    if p not in ancestors(d) and d not in ancestors(p)]
            assert around and free, (p, around, free)
        print("OK")
    """
    assert "OK" in run_py(code, devices=4)


def test_sharded_train_step_matches_single_device():
    """The SAME train step on a 2x4 mesh and on 1 device gives the same
    loss trajectory (SPMD correctness)."""
    code = """
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import get_config
        from repro.launch.mesh import make_mesh
        from repro.models.common import default_plan, partitioned
        from repro.sharding import named_sharding_tree
        from repro.train import (TrainConfig, init_state, make_train_step,
                                 state_specs)
        from repro.optim import AdamWConfig

        cfg = get_config("olmo-1b", smoke=True)
        tcfg = TrainConfig(microbatches=2,
                           optimizer=AdamWConfig(lr=1e-2, total_steps=10))
        key = jax.random.PRNGKey(0)
        batch = {"tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab)}
        batch["targets"] = jnp.roll(batch["tokens"], -1, 1)

        # single-logical run (replicated math)
        state = init_state(cfg, tcfg, key)
        step = jax.jit(make_train_step(cfg, tcfg))
        s1, m1 = step(state, batch)
        l_single = float(m1["loss"])

        # sharded run
        mesh = make_mesh((2, 4), ("data", "model"))
        plan = default_plan()
        cfg2 = partitioned(cfg, plan)
        with jax.set_mesh(mesh):
            st_sh = named_sharding_tree(plan, mesh, state_specs(cfg2, tcfg))
            state2 = init_state(cfg2, tcfg, key)
            state2 = jax.tree.map(jax.device_put, state2, st_sh)
            step2 = jax.jit(make_train_step(cfg2, tcfg),
                            in_shardings=(st_sh, None),
                            out_shardings=(st_sh, None))
            s2, m2 = step2(state2, batch)
        l_shard = float(m2["loss"])
        assert abs(l_single - l_shard) < 5e-3, (l_single, l_shard)
        print("OK", l_single, l_shard)
    """
    out = run_py(code, devices=8)
    assert "OK" in out


def test_collective_path_matches_single_device():
    """On the launcher's (2, 2) mesh a dense olmo takes the collective
    path, and its forward and two train steps agree with one device's:
    the logits at float32 rounding, the losses within the sharded step's
    tolerance above."""
    code = """
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.launch.mesh import make_mesh
        from repro.models import transformer
        from repro.models.common import default_plan, partitioned
        from repro.obs import tp_paths
        from repro.sharding import named_sharding_tree
        from repro.train import (TrainConfig, init_state, make_train_step,
                                 state_specs)
        from repro.optim import AdamWConfig

        cfg = get_config("olmo-1b", smoke=True)
        tcfg = TrainConfig(optimizer=AdamWConfig(lr=1e-2, total_steps=10))
        key = jax.random.PRNGKey(0)
        batch = {"tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab)}
        batch["targets"] = jnp.roll(batch["tokens"], -1, 1)

        def run(cfg, state):
            logits, _ = jax.jit(lambda p, b: transformer.forward(cfg, p, b))(
                state["params"], batch)
            step = jax.jit(make_train_step(cfg, tcfg))
            losses = []
            for _ in range(2):
                state, m = step(state, batch)
                losses.append(float(m["loss"]))
            return logits, losses

        logits1, losses1 = run(cfg, init_state(cfg, tcfg, key))
        mesh = make_mesh((2, 2), ("data", "model"))
        plan = default_plan()
        cfg2 = partitioned(cfg, plan)
        before = tp_paths().snapshot()
        with jax.set_mesh(mesh):
            st_sh = named_sharding_tree(plan, mesh, state_specs(cfg2, tcfg))
            state2 = jax.tree.map(jax.device_put,
                                  init_state(cfg2, tcfg, key), st_sh)
            logits2, losses2 = run(cfg2, state2)
        after = tp_paths().snapshot()
        assert after.get("collective", 0) - before.get("collective", 0) == 2
        assert after.get("gspmd", 0) == before.get("gspmd", 0)
        err = float(jnp.max(jnp.abs(logits1 - logits2))
                    / jnp.max(jnp.abs(logits1)))
        assert err < 1e-5, err
        assert max(abs(a - b) for a, b in zip(losses1, losses2)) < 5e-3, (
            losses1, losses2)
        print("OK", err, losses1, losses2)
    """
    out = run_py(code, devices=4)
    assert "OK" in out


def test_collective_path_bf16_gradients_match_gspmd():
    """In bfloat16 on the (2, 2) mesh the collective path's loss and
    gradients are as close to one device's float32 ones as GSPMD's
    all-reduces are: the loss within the sharded step's tolerance, each
    leaf's gradient norm (the benchmark's ``grad_gap``) within 5e-3, and
    the worst leaf no more than twice GSPMD's.  Measured: loss 2.3e-4 on
    both, ``grad_gap`` 2.2e-3 (collective) and 1.7e-3 (GSPMD)."""
    code = """
        import dataclasses, statistics
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.launch.mesh import make_mesh
        from repro.models import layers as L, zoo
        from repro.models.common import default_plan, partitioned
        from repro.obs import tp_paths
        from repro.sharding import named_sharding_tree
        from repro.train import TrainConfig, init_state, state_specs

        cfg = get_config("olmo-1b", smoke=True)
        tcfg = TrainConfig()
        key = jax.random.PRNGKey(0)
        batch = {"tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab)}
        batch["targets"] = jnp.roll(batch["tokens"], -1, 1)
        params = init_state(cfg, tcfg, key)["params"]

        def grads(c, p):
            return jax.jit(jax.value_and_grad(
                lambda p: zoo.loss_fn(c, p, batch)[0]))(p)

        def norms(g):
            return jax.tree.leaves(jax.tree.map(
                lambda x: float(jnp.linalg.norm(x.astype(jnp.float32))), g))

        loss_ref, g_ref = grads(cfg, params)
        ref = norms(g_ref)
        med = statistics.median(ref)
        mesh = make_mesh((2, 2), ("data", "model"))
        plan = default_plan()
        bf16 = partitioned(dataclasses.replace(cfg, dtype="bfloat16"), plan)
        out = {}
        with jax.set_mesh(mesh):
            sh = named_sharding_tree(plan, mesh, state_specs(bf16, tcfg))
            p2 = jax.tree.map(jax.device_put, params, sh["params"])
            before = tp_paths().snapshot()
            out["collective"] = grads(bf16, p2)
            assert (tp_paths().snapshot().get("collective", 0)
                    > before.get("collective", 0))
            real = L.tp_boundary_path
            L.tp_boundary_path = lambda *a: "gspmd"
            out["gspmd"] = grads(bf16, p2)
            L.tp_boundary_path = real
        gaps = {}
        for path, (loss, g) in out.items():
            assert abs(float(loss) - float(loss_ref)) < 5e-3, (path, loss)
            gaps[path] = max(abs(a - b) / max(b, med)
                             for a, b in zip(norms(g), ref))
        assert max(gaps.values()) < 5e-3, gaps
        assert gaps["collective"] <= 2 * gaps["gspmd"], gaps
        print("OK", gaps)
    """
    out = run_py(code, devices=4)
    assert "OK" in out


def test_train_step_takes_batch_axes_from_cfg():
    """On the launcher's (2,2) mesh, the step of a config partitioned by
    ``default_plan`` constrains its microbatches over the config's batch
    axes: the same program as when they are passed by hand."""
    code = """
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.launch.mesh import make_mesh
        from repro.models.common import default_plan, partitioned
        from repro.sharding import named_sharding_tree
        from repro.train import (TrainConfig, abstract_state,
                                 make_train_step, state_specs)

        plan = default_plan()
        cfg = partitioned(get_config("olmo-1b", smoke=True), plan)
        tcfg = TrainConfig(microbatches=2)
        mesh = make_mesh((2, 2), ("data", "model"))
        with jax.set_mesh(mesh):
            state = jax.tree.map(
                lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                   sharding=sh),
                abstract_state(cfg, tcfg),
                named_sharding_tree(plan, mesh, state_specs(cfg, tcfg)))
            tok = jax.ShapeDtypeStruct(
                (8, 32), jnp.int32, sharding=NamedSharding(mesh, P("data")))
            texts = [jax.jit(step).lower(
                state, {"tokens": tok, "targets": tok}).as_text()
                for step in (make_train_step(cfg, tcfg),
                             make_train_step(cfg, tcfg,
                                             batch_axes=("data",)))]
        assert texts[0] == texts[1]
        print("OK")
    """
    out = run_py(code, devices=4)
    assert "OK" in out


def test_elastic_checkpoint_reshard():
    """Save on a (4,) mesh, restore onto a (2,2) mesh (elastic restart)."""
    code = """
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.train import CheckpointManager

        mesh_a = make_mesh((4,), ("data",))
        sh_a = NamedSharding(mesh_a, P("data"))
        state = {"w": jax.device_put(jnp.arange(16.0), sh_a)}
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d)
            mgr.save(5, state, block=True)
            mesh_b = make_mesh((2, 2), ("x", "y"))
            sh_b = {"w": NamedSharding(mesh_b, P(("x", "y")))}
            restored, _, step = mgr.restore(shardings=sh_b)
            assert step == 5
            np.testing.assert_array_equal(np.asarray(restored["w"]),
                                          np.arange(16.0))
            assert restored["w"].sharding == sh_b["w"]
        print("OK")
    """
    out = run_py(code, devices=8)
    assert "OK" in out


@pytest.mark.slow
def test_tiny_dryrun_cell():
    """The dry-run machinery end-to-end on a small mesh + smoke config."""
    code = """
        import dataclasses, jax
        from repro.configs import get_config, SHAPES
        from repro.launch.dryrun import measure_cell
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_config("olmo-1b", smoke=True)
        cfg = dataclasses.replace(cfg, dtype="bfloat16")
        shape = SHAPES["train_4k"].scaled(seq=128, batch=8)
        rec = measure_cell(cfg, shape, mesh, mesh_name="single",
                           with_cost=True)
        assert rec["fits_hbm"]
        assert rec["flops_per_device"] > 0
        r = rec["roofline"]
        assert r["step_s_overlapped"] > 0
        print("OK", r["dominant"])
    """
    out = run_py(code, devices=8)
    assert "OK" in out
