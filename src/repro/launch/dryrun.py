import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# isort: split  — the two lines above MUST run before any jax import
# (jax locks the device count at first initialization).

import argparse        # noqa: E402
import dataclasses     # noqa: E402
import json            # noqa: E402
import time            # noqa: E402
import traceback       # noqa: E402

import jax             # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCH_IDS, SHAPES, ShapeSpec, get_config, \
    skip_reason  # noqa: E402
from repro.hw import V5E, parse_collectives, dominant_term  # noqa: E402
from repro.launch.mesh import make_production_mesh, pod_size  # noqa: E402
from repro.models import zoo  # noqa: E402
from repro.models.common import (ModelConfig, ShardingPlan, default_plan,
                                 partitioned)  # noqa: E402
from repro.optim import AdamWConfig  # noqa: E402
from repro.serving.kvcache import cache_shardings  # noqa: E402
from repro.sharding import named_sharding_tree  # noqa: E402
from repro.train import (TrainConfig, abstract_state, make_serve_step,
                         make_train_step, state_specs)  # noqa: E402

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh(es); record memory analysis, cost analysis, and the
collective-byte breakdown the roofline reads.

The per-arch TrainConfigs below are the MEMORY-term decisions of the perf
pass (microbatch count, remat, accumulator/moment dtypes).
"""

TRAIN_CFGS: dict[str, TrainConfig] = {
    "llama3-405b": TrainConfig(
        microbatches=16, remat=True, accum_dtype="bfloat16",
        optimizer=AdamWConfig(moment_dtype="bfloat16")),
    "llama4-scout-17b-a16e": TrainConfig(
        microbatches=8, remat=True, accum_dtype="bfloat16",
        optimizer=AdamWConfig(moment_dtype="bfloat16")),
    "mixtral-8x7b": TrainConfig(
        microbatches=8, remat=True,
        optimizer=AdamWConfig(moment_dtype="bfloat16")),
    "granite-3-8b": TrainConfig(microbatches=4, remat=True),
    "codeqwen1.5-7b": TrainConfig(microbatches=4, remat=True),
    "llama-3.2-vision-11b": TrainConfig(microbatches=8, remat=True),
    "olmo-1b": TrainConfig(microbatches=2, remat=True),
    "rwkv6-1.6b": TrainConfig(microbatches=2, remat=True),
    "recurrentgemma-2b": TrainConfig(microbatches=2, remat=True),
    "whisper-small": TrainConfig(microbatches=2, remat=True),
}


def train_config_for(arch: str) -> TrainConfig:
    return TRAIN_CFGS.get(arch, TrainConfig(microbatches=4))


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; no allocation)
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> dict:
    """Global-shape SDS for every model input of this cell."""
    gb, s = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        out = {"tokens": jax.ShapeDtypeStruct((gb, s), jnp.int32),
               "targets": jax.ShapeDtypeStruct((gb, s), jnp.int32)}
    elif shape.kind == "prefill":
        out = {"tokens": jax.ShapeDtypeStruct((gb, s), jnp.int32)}
    else:                                  # decode: one new token
        out = {"token": jax.ShapeDtypeStruct((gb,), jnp.int32),
               "pos": jax.ShapeDtypeStruct((), jnp.int32)}
    if zoo.needs_frontend(cfg) and shape.kind != "decode":
        out["frontend"] = jax.ShapeDtypeStruct(
            (gb, cfg.n_frontend_tokens, cfg.d_model), cfg.activation_dtype)
    if zoo.needs_frontend(cfg) and shape.kind == "decode":
        # decode reads the frontend through the prefilled cross-kv cache
        pass
    return out


def plan_for_shape(cfg: ModelConfig, shape: ShapeSpec,
                   mesh) -> ShardingPlan:
    """Paper-faithful baseline plan adapted to the cell's batch:
    long_500k (batch 1) cannot batch-shard, so the data axis joins the
    kv/sequence sharding group instead of idling."""
    plan = default_plan()
    # sequence parallelism for deep*wide models (train): layer-boundary
    # carries otherwise exceed HBM (found via the 405b cell)
    if shape.kind == "train" and cfg.d_model >= 8192:
        plan.seq_axes = ("model",)
    # expert parallelism requires experts % axis == 0 (mixtral E=8 on a
    # 16-wide axis): fall back to TP inside the expert FFN
    model_size = mesh.shape.get("model", 1)
    if cfg.moe_experts and cfg.moe_experts % model_size:
        plan.rules["expert"] = ()
    axes = set(mesh.axis_names)
    if "pod" in axes:
        # pod axis extends data parallelism (gradient reduction crosses it)
        plan.batch_axes = ("pod", "data")
        plan.rules["embed"] = ("data",)     # FSDP stays in-pod
    if shape.global_batch < mesh.shape.get("data", 1):
        plan.batch_axes = tuple(a for a in plan.batch_axes if a != "data"
                                and a != "pod")
        plan.rules["kv"] = tuple(
            a for a in ("pod", "data", "model") if a in axes)
    return plan


# ---------------------------------------------------------------------------
# lowering one cell
# ---------------------------------------------------------------------------

def _batch_shardings(cfg, shape, plan, mesh, specs):
    batch = tuple(plan.batch_axes) or None
    if isinstance(batch, tuple) and len(batch) == 1:
        batch = batch[0]

    def leaf(sds):
        if len(sds.shape) == 0:
            return NamedSharding(mesh, P())
        parts = [batch] + [None] * (len(sds.shape) - 1)
        return NamedSharding(mesh, P(*parts))

    return jax.tree.map(leaf, specs)


def _cost_dict(compiled) -> dict:
    return compiled.cost_analysis() or {}


def lower_cell(cfg: ModelConfig, shape: ShapeSpec, mesh, *,
               plan: ShardingPlan | None = None,
               tcfg: TrainConfig | None = None,
               unroll: bool = False,
               micro_override: int | None = None,
               compile_only_text: bool = False) -> dict:
    """Lower + compile one (arch x shape x mesh) cell; return its record.

    ``unroll=True`` fully unrolls layer/chunk scans so cost_analysis and
    the collective parse see every iteration (XLA counts while bodies
    once); used by the single-pod COST compile.  ``micro_override``
    forces the microbatch count (cost compiles use 1 and reconstruct)."""
    t0 = time.time()
    plan = plan or plan_for_shape(cfg, shape, mesh)
    tcfg = tcfg or train_config_for(cfg.arch_id)
    if micro_override is not None:
        tcfg = dataclasses.replace(tcfg, microbatches=micro_override)
    cfg = dataclasses.replace(partitioned(cfg, plan), scan_unroll=unroll)
    specs_in = input_specs(cfg, shape)
    n_dev = mesh.devices.size

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            step = make_train_step(cfg, tcfg)
            st_abs = abstract_state(cfg, tcfg)
            st_sh = named_sharding_tree(plan, mesh, state_specs(cfg, tcfg))
            b_sh = _batch_shardings(cfg, shape, plan, mesh, specs_in)
            jitted = jax.jit(step, in_shardings=(st_sh, b_sh),
                             out_shardings=(st_sh, None),
                             donate_argnums=0)
            lowered = jitted.lower(st_abs, specs_in)
        elif shape.kind == "prefill":
            max_len = zoo.cache_max_len(cfg, shape.seq_len)
            params_abs = zoo.abstract(cfg)
            p_sh = named_sharding_tree(plan, mesh, zoo.specs(cfg))
            b_sh = _batch_shardings(cfg, shape, plan, mesh, specs_in)

            def prefill_step(params, batch):
                return zoo.prefill(cfg, params, batch, max_len)

            jitted = jax.jit(prefill_step, in_shardings=(p_sh, b_sh))
            lowered = jitted.lower(params_abs, specs_in)
        else:                               # decode
            max_len = zoo.cache_max_len(cfg, shape.seq_len)
            params_abs = zoo.abstract(cfg)
            p_sh = named_sharding_tree(plan, mesh, zoo.specs(cfg))
            cache_abs = zoo.abstract_cache(cfg, shape.global_batch, max_len)
            model_degree = mesh.shape.get("model", 1)
            c_sh, kv_strategy = cache_shardings(
                cfg, plan, mesh, cache_abs, model_degree=model_degree)
            tok_sh = _batch_shardings(cfg, shape, plan, mesh,
                                      {"token": specs_in["token"]})["token"]
            step = make_serve_step(cfg)
            jitted = jax.jit(step,
                             in_shardings=(p_sh, c_sh, tok_sh, None),
                             out_shardings=(None, c_sh),
                             donate_argnums=1)
            lowered = jitted.lower(params_abs, cache_abs,
                                   specs_in["token"], specs_in["pos"])

        compiled = lowered.compile()

    # ---- analyses -----------------------------------------------------
    cost = _cost_dict(compiled)
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    stats = parse_collectives(hlo, pod_size=pod_size(mesh))

    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    compute_s = V5E.compute_time(flops_dev)
    memory_s = V5E.memory_time(bytes_dev)
    collective_s = V5E.collective_time(stats.ici_link_bytes,
                                       stats.dci_link_bytes)

    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    n_active = cfg.active_param_count()
    model_flops = (6 if shape.kind == "train" else 2) * n_active * tokens
    hlo_flops_global = flops_dev * n_dev

    record = {
        "arch": cfg.arch_id, "shape": shape.name, "kind": shape.kind,
        "unrolled": unroll, "microbatches": (tcfg.microbatches
                                             if shape.kind == "train" else 0),
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "devices": n_dev,
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "collectives": {
            "full_tensor_bytes": stats.raw_operand_bytes,
            "ici_link_bytes": stats.ici_link_bytes,
            "dci_link_bytes": stats.dci_link_bytes,
            "by_kind": {k: {"count": c, "link_bytes": b}
                        for k, (c, b) in stats.by_kind().items()},
        },
        "roofline": {
            "compute_s": compute_s, "memory_s": memory_s,
            "collective_s": collective_s,
            "dominant": dominant_term(compute_s, memory_s, collective_s),
            "step_s_overlapped": max(compute_s, memory_s, collective_s),
        },
        "model_flops_global": model_flops,
        "hlo_flops_global": hlo_flops_global,
        "useful_flops_ratio": (model_flops / hlo_flops_global
                               if hlo_flops_global else None),
        "memory_analysis": {
            k: int(getattr(mem, k))
            for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                      "output_size_in_bytes", "alias_size_in_bytes",
                      "generated_code_size_in_bytes")
            if hasattr(mem, k)
        },
        "compile_seconds": time.time() - t0,
    }
    if shape.kind == "decode":
        record["kv_strategy"] = kv_strategy
    if compile_only_text:
        record["hlo_lines"] = len(hlo.splitlines())
    return record


# ---------------------------------------------------------------------------
# cost reconstruction: COST = m * (C_m1 - C_opt) + C_opt
# ---------------------------------------------------------------------------

def optimizer_cost(cfg: ModelConfig, mesh, plan: ShardingPlan,
                   tcfg: TrainConfig) -> dict:
    """Lower the AdamW update alone (elementwise, no while loops) to
    separate the per-step optimizer cost from the per-microbatch cost."""
    from repro.optim import adamw_update, abstract_opt_state

    params_abs = zoo.abstract(cfg)
    opt_abs = abstract_opt_state(tcfg.optimizer, params_abs)
    acc_dt = jnp.dtype(tcfg.accum_dtype)
    grads_abs = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, acc_dt), params_abs)
    pspecs = zoo.specs(cfg)
    p_sh = named_sharding_tree(plan, mesh, pspecs)
    o_sh = {"mu": p_sh, "nu": p_sh,
            "step": NamedSharding(mesh, P())}

    def opt_only(params, opt, grads):
        new_p, new_o, m = adamw_update(tcfg.optimizer, grads, opt, params)
        return new_p, new_o, m["grad_norm"]

    with jax.set_mesh(mesh):
        jitted = jax.jit(opt_only, in_shardings=(p_sh, o_sh, p_sh),
                         out_shardings=(p_sh, o_sh, None),
                         donate_argnums=(0, 1))
        compiled = jitted.lower(params_abs, opt_abs, grads_abs).compile()
    cost = _cost_dict(compiled)
    stats = parse_collectives(compiled.as_text(), pod_size=pod_size(mesh))
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "ici": stats.ici_link_bytes, "dci": stats.dci_link_bytes}


def reconstruct_train_cost(c1: dict, opt: dict, m: int) -> dict:
    """Combine the m=1 unrolled cost compile with the optimizer-only cost:
    per-step = m * (C_m1 - C_opt) + C_opt (clamped at C_m1)."""
    out = {}
    for key, c1k in (("flops", "flops_per_device"),
                     ("bytes", "bytes_per_device")):
        base = max(c1[c1k] - opt[key], 0.0)
        out[c1k] = m * base + opt[key]
    ici1 = c1["collectives"]["ici_link_bytes"]
    dci1 = c1["collectives"]["dci_link_bytes"]
    out["ici_link_bytes"] = m * max(ici1 - opt["ici"], 0.0) + opt["ici"]
    out["dci_link_bytes"] = m * max(dci1 - opt["dci"], 0.0) + opt["dci"]
    return out


def depth_plan(cfg: ModelConfig) -> tuple[int, int, float, float] | None:
    """(L_a, L_b, units_per_layer_a..) for cost extrapolation, or None for a
    direct unrolled compile.  Returns (La, Lb, units_a, units_b, units_full)
    in scan-unit space (layers, or super-blocks for patterned archs)."""
    if cfg.family == "hybrid":
        per = len(cfg.block_pattern)
        ns, tail = cfg.n_layers // per, cfg.n_layers % per
        # tail rglru layers counted as fractional super-blocks
        return (2 * per, 4 * per, 2.0, 4.0, ns + tail / per)
    if cfg.family == "vlm":
        per = cfg.cross_attn_every
        return (2 * per, 4 * per, 2.0, 4.0, cfg.n_layers / per)
    if cfg.family == "ssm":
        # each layer unrolls S/chunk WKV bodies: keep depths small
        return (4, 8, 4.0, 8.0, float(cfg.n_layers))
    if cfg.n_layers > 16:
        return (8, 16, 8.0, 16.0, float(cfg.n_layers))
    return None


def _extract(rec: dict) -> dict:
    return {"flops_per_device": rec["flops_per_device"],
            "bytes_per_device": rec["bytes_per_device"],
            "ici_link_bytes": rec["collectives"]["ici_link_bytes"],
            "dci_link_bytes": rec["collectives"]["dci_link_bytes"]}


def extrapolated_cost(cfg: ModelConfig, shape: ShapeSpec, mesh, plan,
                      tcfg) -> tuple[dict, dict, float]:
    """Unrolled cost compiles at two reduced depths, linear extrapolation
    to the full depth (costs are exactly per-layer-linear; XLA while-body
    once-counting and full-depth unroll RAM blowups are both avoided).
    Returns (metrics, collectives_record_of_Lb, compile_seconds).

    For train cells the compile uses ONE microbatch at the PER-MICRO
    global batch (B/m); measure_cell multiplies back."""
    la, lb, ua, ub, uf = depth_plan(cfg)
    micro = 1 if shape.kind == "train" else None
    if shape.kind == "train":
        shape = shape.scaled(batch=shape.global_batch // tcfg.microbatches)
    ca = lower_cell(dataclasses.replace(cfg, n_layers=la), shape, mesh,
                    plan=plan, tcfg=tcfg, unroll=True, micro_override=micro)
    jax.clear_caches()
    cb = lower_cell(dataclasses.replace(cfg, n_layers=lb), shape, mesh,
                    plan=plan, tcfg=tcfg, unroll=True, micro_override=micro)
    jax.clear_caches()
    a, b = _extract(ca), _extract(cb)
    out = {}
    for key in a:
        per_unit = (b[key] - a[key]) / (ub - ua)
        out[key] = max(a[key] + per_unit * (uf - ua), 0.0)
    return out, cb["collectives"], \
        ca["compile_seconds"] + cb["compile_seconds"]


def measure_cell(cfg: ModelConfig, shape: ShapeSpec, mesh, *,
                 mesh_name: str, plan: ShardingPlan | None = None,
                 tcfg: TrainConfig | None = None,
                 with_cost: bool = True) -> dict:
    """Full measurement: rolled compile (memory/fits/compile-proof on any
    mesh) + — single-pod only — unrolled cost compile with microbatch
    reconstruction feeding the roofline terms."""
    plan = plan or plan_for_shape(cfg, shape, mesh)
    tcfg = tcfg or train_config_for(cfg.arch_id)
    rec = lower_cell(cfg, shape, mesh, plan=plan, tcfg=tcfg)
    rec["mesh_name"] = mesh_name
    per_dev, fits = hbm_check(rec)
    rec["hbm_bytes_per_device_est"] = per_dev
    rec["fits_hbm"] = fits
    if not with_cost:
        rec["roofline"]["note"] = "rolled-scan costs (undercounted); "             "single-pod cost compile carries the roofline"
        return rec

    jax.clear_caches()
    dp = depth_plan(cfg)
    cost_compile_s = 0.0
    if dp is not None:
        c1, coll_rec, cost_compile_s = extrapolated_cost(
            cfg, shape, mesh, plan, tcfg)
        rec["cost_extrapolated_from"] = dp[:2]
    elif shape.kind == "train":
        shape_micro = shape.scaled(
            batch=shape.global_batch // tcfg.microbatches)
        cost_rec = lower_cell(cfg, shape_micro, mesh, plan=plan, tcfg=tcfg,
                              unroll=True, micro_override=1)
        c1 = _extract(cost_rec)
        coll_rec = cost_rec["collectives"]
        cost_compile_s = cost_rec["compile_seconds"]
    else:
        cost_rec = lower_cell(cfg, shape, mesh, plan=plan, tcfg=tcfg,
                              unroll=True)
        c1 = _extract(cost_rec)
        coll_rec = cost_rec["collectives"]
        cost_compile_s = cost_rec["compile_seconds"]

    if shape.kind == "train":
        opt = optimizer_cost(cfg, mesh, plan, tcfg)
        rec["optimizer_cost"] = opt
        m = tcfg.microbatches
        fixed = {}
        for key, okey in (("flops_per_device", "flops"),
                          ("bytes_per_device", "bytes"),
                          ("ici_link_bytes", "ici"),
                          ("dci_link_bytes", "dci")):
            base = max(c1[key] - opt[okey], 0.0)
            fixed[key] = m * base + opt[okey]
    else:
        fixed = c1

    rec["flops_per_device"] = fixed["flops_per_device"]
    rec["bytes_per_device"] = fixed["bytes_per_device"]
    rec["collectives"] = coll_rec
    rec["collectives"]["ici_link_bytes_step"] = fixed["ici_link_bytes"]
    rec["collectives"]["dci_link_bytes_step"] = fixed["dci_link_bytes"]
    compute_s = V5E.compute_time(fixed["flops_per_device"])
    # two memory accountings:
    #  * memory_s_hlo — the spec formula HLO_bytes/(chips*bw).  The CPU
    #    backend's cost analysis counts every unfused elementwise
    #    operand, so this is a severe UPPER bound (5-10x on TPU, where
    #    fusion keeps those values in registers/VMEM).
    #  * memory_s — buffer-traffic estimate from the rolled compile's
    #    real buffer assignment: (args + outputs + 3*temp)/bw (the x3
    #    models fwd+bwd+remat re-traffic).  Used for dominance.
    ma = rec.get("memory_analysis", {})
    traffic = (ma.get("argument_size_in_bytes", 0)
               + ma.get("output_size_in_bytes", 0)
               + 3 * ma.get("temp_size_in_bytes", 0))
    memory_s_hlo = V5E.memory_time(fixed["bytes_per_device"])
    memory_s = V5E.memory_time(traffic)
    collective_s = V5E.collective_time(fixed["ici_link_bytes"],
                                       fixed["dci_link_bytes"])
    n_dev = mesh.devices.size
    hlo_flops_global = fixed["flops_per_device"] * n_dev
    rec["hlo_flops_global"] = hlo_flops_global
    rec["useful_flops_ratio"] = (rec["model_flops_global"] / hlo_flops_global
                                 if hlo_flops_global else None)
    rec["roofline"] = {
        "compute_s": compute_s, "memory_s": memory_s,
        "memory_s_hlo": memory_s_hlo,
        "collective_s": collective_s,
        "dominant": dominant_term(compute_s, memory_s, collective_s),
        "step_s_overlapped": max(compute_s, memory_s, collective_s),
    }
    rec["cost_compile_seconds"] = cost_compile_s
    return rec


# ---------------------------------------------------------------------------

def hbm_check(record: dict) -> tuple[float, bool]:
    # memory_analysis is PER-DEVICE (the SPMD module is the per-device
    # program; verified empirically)
    ma = record.get("memory_analysis", {})
    per_dev = (ma.get("argument_size_in_bytes", 0)
               + ma.get("temp_size_in_bytes", 0)
               + ma.get("output_size_in_bytes", 0)
               - ma.get("alias_size_in_bytes", 0))
    return per_dev, per_dev <= V5E.hbm_bytes


def run_cells(archs, shapes, meshes, out_path, *, verbose=True):
    results = []
    for mesh_name in meshes:
        mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
        for arch in archs:
            cfg = get_config(arch)
            for shape_name in shapes:
                shape = SHAPES[shape_name]
                reason = skip_reason(cfg, shape)
                if reason:
                    results.append({"arch": arch, "shape": shape_name,
                                    "mesh": mesh_name, "skipped": reason})
                    if verbose:
                        print(f"[skip] {mesh_name:6s} {arch:24s} "
                              f"{shape_name:12s} {reason}")
                    continue
                try:
                    rec = measure_cell(cfg, shape, mesh,
                                       mesh_name=mesh_name,
                                       with_cost=(mesh_name == "single"))
                    results.append(rec)
                    if verbose:
                        r = rec["roofline"]
                        print(f"[ok]   {mesh_name:6s} {arch:24s} "
                              f"{shape_name:12s} compile={rec['compile_seconds']:6.1f}s "
                              f"dom={r['dominant']:10s} "
                              f"step={r['step_s_overlapped']*1e3:9.3f}ms "
                              f"hbm/dev={rec['hbm_bytes_per_device_est']/2**30:6.2f}GiB "
                              f"fits={rec['fits_hbm']}")
                except Exception as e:  # noqa: BLE001 — record and continue
                    results.append({"arch": arch, "shape": shape_name,
                                    "mesh": mesh_name, "error": str(e),
                                    "traceback": traceback.format_exc()})
                    if verbose:
                        print(f"[FAIL] {mesh_name:6s} {arch:24s} "
                              f"{shape_name:12s} {e}")
                # free compilation caches between cells
                jax.clear_caches()
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(results, f, indent=1)
        if verbose:
            print(f"wrote {out_path} ({len(results)} records)")
    return results


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default="all",
                    help=f"arch id or 'all' ({', '.join(ARCH_IDS)})")
    ap.add_argument("--shape", default="all",
                    help=f"shape or 'all' ({', '.join(SHAPES)})")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun.json")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = (["single", "multi"] if args.mesh == "both" else [args.mesh])
    run_cells(archs, shapes, meshes, args.out)


if __name__ == "__main__":
    main()
