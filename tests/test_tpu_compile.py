"""Compile the three Pallas kernels, and a train step through the flash
kernel on one chip and on the four chips' (2,2) mesh, for a described
TPU v5e, no chip needed.

The TPU compiler is installed with jax; it compiles for a chip that is
described and not attached, and refuses what the chip would refuse
(unaligned tiles, too much VMEM) where interpret mode does not.  Shapes
are the published widths ``chip_smoke.py`` runs on the chip.  Code that
asks the default backend for its platform still sees the CPU here, so
the train step's test steers ``models.layers`` onto the kernel path.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention.ops import flash_attention
from repro.models import layers
from repro.obs import attention_paths
from repro.kernels.rglru.ops import rglru
from repro.kernels.rwkv6.ops import wkv6


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        # the TPU library writes its logs under /tmp unless told not to
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        from jax.experimental import topologies
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A described-chip compile is written to the persistent cache but
    cannot be read back without a chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return fn.lower(*args).compile().as_text()


def test_flash_attention_compiles_for_v5e(one_chip, no_compile_cache):
    qkv = ((1, 2048, 16, 128), jnp.bfloat16)          # olmo-1b
    text = _compiled_text(flash_attention, one_chip, qkv, qkv, qkv)
    assert "tpu_custom_call" in text


def test_flash_attention_grad_compiles_for_v5e(one_chip, no_compile_cache):
    """Forward and both backward kernels through the custom_vjp, at the
    olmo-1b-8l.pretrain-2k cell's shape."""
    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    qkv = ((2, 2048, 16, 128), jnp.bfloat16)
    text = _compiled_text(jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
                          one_chip, qkv, qkv, qkv)
    assert text.count("custom_call_target=\"tpu_custom_call\"") >= 3


@pytest.mark.parametrize("platform,path", [("tpu", "flash"),
                                           ("cpu", "dense")])
def test_olmo_layer_step_scores_stay_off_hbm(one_chip, no_compile_cache,
                                             monkeypatch, platform, path):
    """A one-layer olmo-1b train step at the cell's batch 2 x 2048 with
    full remat: on the kernel path no f32[2,16,2048,2048] score buffer is
    left in the compiled program; the jnp path has one."""
    from repro.train import TrainConfig, abstract_state, make_train_step
    monkeypatch.setattr(layers, "_platform", lambda: platform)
    cfg = dataclasses.replace(get_config("olmo-1b"), n_layers=1)
    tcfg = TrainConfig()

    def place(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    state = jax.tree.map(place, abstract_state(cfg, tcfg))
    tok = place(jax.ShapeDtypeStruct((2, 2048), jnp.int32))
    before = attention_paths().snapshot().get(path, 0)
    text = jax.jit(make_train_step(cfg, tcfg)).lower(
        state, {"tokens": tok, "targets": tok}).compile().as_text()
    assert attention_paths().snapshot().get(path, 0) > before
    assert ("f32[2,16,2048,2048]" in text) == (path == "dense")
    assert ("tpu_custom_call" in text) == (path == "flash")


@pytest.fixture(scope="module")
def mesh_step_texts(topo):
    """A one-layer olmo-1b train step at the four-chip cell's batch
    16 x 2048, on the launcher's (data 2, model 2) mesh and
    ``default_plan``, compiled for the described v5e:2x2 on the kernel
    path (``"tpu"``) and on the jnp path (``"cpu"``): platform -> (HLO
    text, attention paths counted while tracing it)."""
    from jax.experimental.compilation_cache import compilation_cache
    from repro.launch.train import jit_train_step
    from repro.models.common import default_plan, partitioned
    from repro.sharding import batch_sharding, named_sharding_tree
    from repro.train import TrainConfig, abstract_state, state_specs
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    plan = default_plan()
    cfg = partitioned(dataclasses.replace(get_config("olmo-1b"), n_layers=1),
                      plan)
    tcfg = TrainConfig()
    texts = {}
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp, jax.set_mesh(mesh):
            state = jax.tree.map(
                lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                   sharding=sh),
                abstract_state(cfg, tcfg),
                named_sharding_tree(plan, mesh, state_specs(cfg, tcfg)))
            tok = jax.ShapeDtypeStruct((16, 2048), jnp.int32,
                                       sharding=batch_sharding(plan, mesh))
            for platform in ("tpu", "cpu"):
                mp.setattr(layers, "_platform", lambda p=platform: p)
                before = attention_paths().snapshot()
                text = jit_train_step(cfg, tcfg, plan).lower(
                    state, {"tokens": tok, "targets": tok}
                ).compile().as_text()
                after = attention_paths().snapshot()
                texts[platform] = text, {
                    p: after.get(p, 0) - before.get(p, 0)
                    for p in ("flash", "dense")}
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()
    return texts


@pytest.mark.parametrize("platform,path", [("tpu", "flash"),
                                           ("cpu", "dense")])
def test_olmo_mesh_step_maps_the_kernel_without_resharding(
        mesh_step_texts, platform, path):
    """On the kernel path the kernel runs per shard, each device's
    f32[8,8,2048,2048] scores are gone, and the step runs the same
    collectives, of the same sizes, as the jnp path: the map's boundary
    reshards nothing."""
    from repro.obs import count_collectives
    text, paths = mesh_step_texts[platform]
    other = "dense" if path == "flash" else "flash"
    assert paths[path] > 0 and paths[other] == 0
    assert ("tpu_custom_call" in text) == (path == "flash")
    assert ("f32[8,8,2048,2048]" in text) == (path == "dense")
    assert (count_collectives(text)
            == count_collectives(mesh_step_texts["cpu"][0]))


@pytest.fixture(scope="module")
def mesh_step_gspmd_text(topo):
    """``mesh_step_texts``' step on the kernel path with every block's
    tensor-parallel boundaries left to GSPMD (``tp_boundary_path`` steered
    to ``"gspmd"``): the program the collective matmuls replace."""
    from jax.experimental.compilation_cache import compilation_cache
    from repro.launch.train import jit_train_step
    from repro.models.common import default_plan, partitioned
    from repro.sharding import batch_sharding, named_sharding_tree
    from repro.train import TrainConfig, abstract_state, state_specs
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    plan = default_plan()
    cfg = partitioned(dataclasses.replace(get_config("olmo-1b"), n_layers=1),
                      plan)
    tcfg = TrainConfig()
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp, jax.set_mesh(mesh):
            mp.setattr(layers, "_platform", lambda: "tpu")
            mp.setattr(layers, "tp_boundary_path", lambda *a: "gspmd")
            state = jax.tree.map(
                lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                   sharding=sh),
                abstract_state(cfg, tcfg),
                named_sharding_tree(plan, mesh, state_specs(cfg, tcfg)))
            tok = jax.ShapeDtypeStruct((16, 2048), jnp.int32,
                                       sharding=batch_sharding(plan, mesh))
            return jit_train_step(cfg, tcfg, plan).lower(
                state, {"tokens": tok, "targets": tok}).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _block_all_reduces(text: str) -> list[str]:
    """The all-reduces of (8, 2048, 2048) bf16 activations, one device's
    share of the cell's (16, 2048, 2048), inside the block's scopes."""
    return [line for line in text.splitlines()
            if re.search(r"= bf16\[8,2048,2048\]\S* all-reduce(-start)?\(",
                         line)
            and re.search(r'op_name="[^"]*/(attention|mlp)/', line)]


def _permutes_under_dots(text: str) -> list[int]:
    """For each collective-permute-start, the matmul fusions the schedule
    runs before its done."""
    out, starts = [], {}
    for line in text.splitlines():
        m = re.match(r"\s*%(\S+) = .* collective-permute-start\(", line)
        if m:
            starts[m.group(1)] = 0
            continue
        m = re.match(r"\s*%\S+ = .* collective-permute-done\(%(\S+)\)",
                     line)
        if m and m.group(1) in starts:
            out.append(starts.pop(m.group(1)))
        elif "kind=kOutput" in line and "dot_general" in line:
            starts = {k: n + 1 for k, n in starts.items()}
    return out


def test_olmo_mesh_step_hides_the_tp_sums_under_matmuls(
        mesh_step_texts, mesh_step_gspmd_text):
    """On the collective path the block sums its activations over the
    ``model`` axis with no all-reduce: collective-permutes, each with a
    matmul scheduled under it, carry them, and the step's collective bytes
    stay within 15% of GSPMD's, whose five all-reduces are there."""
    from repro.obs import count_collectives
    text, _ = mesh_step_texts["tpu"]
    assert _block_all_reduces(mesh_step_gspmd_text)
    assert not _block_all_reduces(text)
    under = _permutes_under_dots(text)
    assert under and min(under) > 0, under

    def total(t):
        return sum(c["bytes"] for c in count_collectives(t).values())

    assert total(text) <= 1.15 * total(mesh_step_gspmd_text), (
        count_collectives(text), count_collectives(mesh_step_gspmd_text))


def test_rglru_compiles_for_v5e(one_chip, no_compile_cache):
    ax = ((1, 2048, 2560), jnp.float32)               # recurrentgemma-2b
    text = _compiled_text(rglru, one_chip, ax, ax)
    assert "tpu_custom_call" in text


def test_wkv6_compiles_for_v5e(one_chip, no_compile_cache):
    rkvw = ((1, 32, 2048, 64), jnp.float32)           # rwkv6-1.6b
    text = _compiled_text(wkv6, one_chip, rkvw, rkvw, rkvw, rkvw,
                          ((32, 64), jnp.float32))
    assert "tpu_custom_call" in text
