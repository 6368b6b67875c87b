"""ShardingPlan -> concrete NamedShardings; the autotuner's output surface.

The paper-technique integration point (DESIGN.md §4): the autotuner's
per-op-class shard degrees materialize here as a ``ShardingPlan`` whose
rules map logical axes to mesh axes.  ``plan_from_degrees`` converts a
``ShardPlanResult`` (degrees per op class) into rules on a mesh whose
``model`` axis has been factored into sub-axes — degree-8 sharding on a
16-wide model axis is expressed by splitting the axis into ('mdl', 'sub')
and assigning only 'mdl'.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models.common import ModelConfig, ShardingPlan

# op classes the tuner knows, and the logical axes each one controls
OP_CLASS_AXES: dict[str, tuple[str, ...]] = {
    "attention": ("heads", "kv"),
    "mlp": ("ff",),
    "moe": ("expert",),
    "embed": ("vocab",),
    "recurrence": ("state",),
}


def named_sharding_tree(plan: ShardingPlan, mesh: Mesh, logical_tree):
    """Map a logical-axes spec tree to NamedShardings on ``mesh``.

    A leaf on no mesh axis gets ``P()``, the form a jitted step returns it
    in: ``jit`` keys its cache on the spec as written, so ``P(None, None)``
    would compile the step again at its second call."""
    def leaf(spec: tuple) -> NamedSharding:
        pspec = plan.spec_for(spec)
        return NamedSharding(mesh, pspec if any(pspec) else P())
    return jax.tree.map(
        leaf, logical_tree,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(e, (str, type(None))) for e in x))


def batch_sharding(plan: ShardingPlan, mesh: Mesh, *,
                   seq_dim: int | None = None) -> NamedSharding:
    """(B, S, ...) activation sharding: batch over plan.batch_axes, and
    optionally sequence over plan.seq_axes (sequence parallelism)."""
    parts: list = [tuple(plan.batch_axes) or None]
    if seq_dim is not None:
        parts.append(tuple(plan.seq_axes) or None)
    return NamedSharding(mesh, P(*parts))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def degree_to_axes(degree: int, model_axes: tuple[tuple[str, int], ...]
                   ) -> tuple[str, ...]:
    """Greedily pick mesh sub-axes whose product equals ``degree``.

    model_axes: ((name, size), ...) in preference order (ICI-near first).
    degree must be a product of a prefix of the sizes."""
    axes: list[str] = []
    left = degree
    for name, size in model_axes:
        if left <= 1:
            break
        if left % size == 0:
            axes.append(name)
            left //= size
        elif size % left == 0 and left > 1:
            # would need a partial axis: not expressible -> caller must
            # factor the mesh so degrees are products of sub-axis sizes
            raise ValueError(
                f"degree {degree} not expressible with axes {model_axes}")
    if left != 1:
        raise ValueError(
            f"degree {degree} not expressible with axes {model_axes}")
    return tuple(axes)


def plan_from_degrees(degrees: dict[str, int],
                      model_axes: tuple[tuple[str, int], ...],
                      *, fsdp_axes: tuple[str, ...] = ("data",),
                      batch_axes: tuple[str, ...] = ("data",),
                      ) -> ShardingPlan:
    """Build a ShardingPlan from per-op-class shard degrees (the frozen
    Strategy-1/2 output of the autotuner)."""
    rules: dict[str, tuple[str, ...]] = {
        "embed": tuple(fsdp_axes),
        "layers": (), "conv": (),
    }
    for cls, logical_axes in OP_CLASS_AXES.items():
        deg = degrees.get(cls, 1)
        axes = degree_to_axes(deg, model_axes)
        for la in logical_axes:
            rules[la] = axes
    # kv heads cannot shard beyond their count: the caller clamps the
    # attention degree; here we simply mirror it
    return ShardingPlan(rules=rules, batch_axes=batch_axes)


def clamp_degree_for_axis(degree: int, axis_len: int) -> int:
    """Largest power-of-two divisor of axis_len that is <= degree."""
    d = 1
    while d * 2 <= min(degree, axis_len) and axis_len % (d * 2) == 0:
        d *= 2
    return d


def validate_plan(cfg: ModelConfig, plan: ShardingPlan, mesh: Mesh) -> list[str]:
    """Static divisibility checks: every sharded dim must divide evenly.
    Returns a list of problems (empty = ok)."""
    problems = []
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def axes_size(axes: tuple[str, ...]) -> int:
        n = 1
        for a in axes:
            n *= sizes.get(a, 1)
        return n

    # param dims are stored flattened (heads*hd), so divisibility is on the
    # flattened sizes; head-granularity locality is a perf matter the
    # autotuner discovers through the collective term, not a validity one.
    checks = {
        "heads": cfg.n_heads * cfg.hd, "kv": cfg.n_kv_heads * cfg.hd,
        "ff": cfg.d_ff, "vocab": cfg.vocab, "embed": cfg.d_model,
        "expert": cfg.moe_experts or 1,
    }
    for axis_name, dim in checks.items():
        deg = axes_size(plan.rules.get(axis_name, ()))
        if deg > 1 and dim % deg:
            problems.append(f"{axis_name}={dim} not divisible by {deg}")
    return problems
