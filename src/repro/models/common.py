"""Model substrate: configs, parameter pytrees with logical sharding axes.

Design:

* Models are pure functions over parameter pytrees (nested dicts of
  ``jnp.ndarray``).  No module framework — only jax.
* Every parameter carries a *logical axis spec* (tuple of logical axis
  names, one per array dim) in a parallel pytree.  A ``ShardingPlan``
  maps logical names → mesh axes; this mapping is THE knob the paper-
  technique autotuner turns (per-op-class shard degree).  ``partitioned``
  copies the plan's partition of the activations onto a ``ModelConfig``.
* ``abstract_params`` builds the same pytree out of ShapeDtypeStruct —
  the dry-run lowers against it without allocating a single byte.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

Params = Any          # nested dict pytree of arrays
Specs = Any           # same treedef, leaves = tuple[str|None, ...]


# ---------------------------------------------------------------------------
# Architecture config
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """One config covers every assigned family via optional fields."""

    arch_id: str
    family: str                      # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0                # 0 -> d_model // n_heads
    # MoE
    moe_experts: int = 0
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    # attention flavor
    window: int | None = None        # sliding-window size (Mixtral SWA, local)
    rope_theta: float = 10000.0
    # norms / activations
    norm: str = "rms"                # rms | layernorm | nonparam
    act: str = "swiglu"              # swiglu | gelu
    # hybrid (recurrentgemma): layer pattern, e.g. ("rglru","rglru","attn")
    block_pattern: tuple[str, ...] = ()
    rglru_dim: int = 0               # recurrence width (0 -> d_model)
    # ssm (rwkv6)
    # vlm: insert a cross-attn layer every k self-attn layers
    cross_attn_every: int = 0
    n_frontend_tokens: int = 0       # stub modality tokens (vlm/audio)
    # enc-dec (whisper)
    encoder_layers: int = 0
    # numerics
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    # per-layer rematerialization: "none" | "full" — "full" wraps every
    # layer-scan body in jax.checkpoint so the backward pass stores only
    # scan carries (layer inputs), not stacked per-layer residuals
    remat: str = "none"
    # fully unroll layer/chunk scans (dry-run COST compiles only): XLA's
    # cost analysis counts while-loop bodies ONCE, so rolled scans
    # undercount flops/bytes/collectives by the trip count
    scan_unroll: bool = False
    # batch_axes, seq_axes and head_axes: the plan's partition of the
    # activations, which ``partitioned`` copies from a ShardingPlan
    # mesh axes the activation batch dim is sharded over; when non-empty,
    # layer bodies emit with_sharding_constraint on their (B,S,D)
    # activations — remat/scan boundary tensors otherwise lose their
    # sharding and GSPMD resolves them replicated (found in the dry-run)
    batch_axes: tuple = ()
    # sequence parallelism (Korthikanti et al.): shard the SEQ dim of
    # layer-boundary activations over these axes — for deep/wide models
    # the per-microbatch stacked scan carries (L,B,S,D) otherwise exceed
    # HBM (llama3-405b: 15.8 GiB/device of carries at 1 seq/device)
    seq_axes: tuple = ()
    # mesh axes the attention heads (q and kv alike) are sharded over:
    # the plan's "heads" rule
    head_axes: tuple = ()
    tie_embeddings: bool = False
    logit_softcap: float = 0.0

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded up to 256 so vocab-sharded params divide evenly
        on any mesh factorization (Megatron-style padding; pad ids are
        never targets)."""
        return -(-self.vocab // 256) * 256

    @property
    def is_subquadratic(self) -> bool:
        """Supports 500k-token decode: recurrent state or bounded window."""
        return (self.family in ("ssm", "hybrid")
                or self.window is not None)

    @property
    def activation_dtype(self) -> jnp.dtype:
        return jnp.dtype(self.dtype)

    def param_count(self) -> int:
        """Exact count from the abstract parameter tree."""
        tree = abstract_params_for(self)
        return int(sum(math.prod(l.shape) for l in jax.tree.leaves(tree)))

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only routed experts)."""
        total = self.param_count()
        if not self.moe_experts:
            return total
        expert = 3 * self.d_model * self.d_ff  # gate/up/down per expert
        inactive = (self.moe_experts - self.moe_top_k) * expert * self.n_layers
        return total - inactive


# late import hook — zoo registers the builder to avoid circular imports
_ABSTRACT_BUILDERS: dict[str, Any] = {}


def register_family(family: str, abstract_fn) -> None:
    _ABSTRACT_BUILDERS[family] = abstract_fn


def abstract_params_for(cfg: ModelConfig):
    from repro.models import zoo  # noqa: F401  (ensures registration)
    return _ABSTRACT_BUILDERS[cfg.family](cfg)


# ---------------------------------------------------------------------------
# Logical sharding axes
# ---------------------------------------------------------------------------

# Canonical logical axis names used by every model family:
#   "embed"   d_model dim            "ff"     mlp hidden dim
#   "heads"   q-head dim             "kv"     kv-head dim
#   "vocab"   vocabulary dim         "expert" MoE expert dim
#   "layers"  stacked scan dim       None     replicated
LOGICAL_AXES = ("embed", "ff", "heads", "kv", "vocab", "expert", "layers",
                "conv", "state", "table_d")


@dataclasses.dataclass
class ShardingPlan:
    """logical axis -> tuple of mesh axes.  THE tunable object: the
    shard-degree autotuner rewrites entries (e.g. 'ff' -> ('model',) at
    degree 16, or 'ff' -> () at degree 1).

    ``batch_axes``/``seq_axes`` control activation shardings."""

    rules: dict[str, tuple[str, ...]]
    batch_axes: tuple[str, ...] = ("data",)
    seq_axes: tuple[str, ...] = ()

    def spec_for(self, logical: tuple[str | None, ...]) -> P:
        parts = []
        used: set[str] = set()
        for name in logical:
            axes = self.rules.get(name, ()) if name else ()
            # a mesh axis may appear at most once per spec: first
            # occurrence wins (e.g. MoE (expert, embed, ff) keeps expert
            # parallelism on the model axis and leaves ff unsharded;
            # rwkv (embed, embed) square weights shard one dim)
            axes = tuple(a for a in axes if a not in used)
            used.update(axes)
            if len(axes) == 0:
                parts.append(None)
            elif len(axes) == 1:
                parts.append(axes[0])
            else:
                parts.append(tuple(axes))
        return P(*parts)

    def tree_specs(self, logical_tree: Specs) -> Any:
        return jax.tree.map(
            self.spec_for, logical_tree,
            is_leaf=lambda x: isinstance(x, tuple)
            and all(isinstance(e, (str, type(None))) for e in x))


def partitioned(cfg: ModelConfig, plan: ShardingPlan) -> ModelConfig:
    """``cfg`` with ``plan``'s partition of the activations: the batch and
    sequence axes, and the heads' axes the attention kernel is mapped
    over."""
    return dataclasses.replace(cfg, batch_axes=tuple(plan.batch_axes),
                               seq_axes=tuple(plan.seq_axes),
                               head_axes=tuple(plan.rules.get("heads", ())))


def default_plan() -> ShardingPlan:
    """Paper-faithful baseline: uniform max shard degree on the model axis
    for every op class (the analogue of TF's 'one intra-op parallelism for
    all operations'), FSDP on the data axis over the embed dim."""
    return ShardingPlan(rules={
        "embed": ("data",),       # FSDP: gather at use
        "ff": ("model",),
        "heads": ("model",),
        "kv": ("model",),
        "vocab": ("model",),      # unembed projection (matmul, shards cleanly)
        "expert": ("model",),
        "layers": (),
        "conv": (),
        "state": (),
        # input embedding TABLE: clean 1-D vocab sharding — GSPMD then
        # partitions the token gather as masked-gather + all-reduce (the
        # Megatron pattern) and the tied unembed keeps logits
        # vocab-sharded.  (A 2-D-sharded table triggered XLA involuntary
        # full rematerialization; found in the first dry-run.)
        "table_d": (),
    })


def replicated_plan() -> ShardingPlan:
    return ShardingPlan(rules={k: () for k in LOGICAL_AXES},
                        batch_axes=(), seq_axes=())


# ---------------------------------------------------------------------------
# Param tree construction helpers
# ---------------------------------------------------------------------------

class TreeBuilder:
    """Collects (params, logical_specs) pairs with optional abstract mode."""

    def __init__(self, cfg: ModelConfig, key: jax.Array | None,
                 abstract: bool = False):
        self.cfg = cfg
        self.abstract = abstract
        self._key = key
        self.params: dict = {}
        self.specs: dict = {}

    def _next_key(self) -> jax.Array:
        assert self._key is not None
        self._key, sub = jax.random.split(self._key)
        return sub

    def leaf(self, path: str, shape: tuple[int, ...],
             logical: tuple[str | None, ...], *,
             init: str = "normal", scale: float | None = None):
        """Register one parameter array at a '/'-separated path."""
        assert len(shape) == len(logical), (path, shape, logical)
        dtype = jnp.dtype(self.cfg.param_dtype)
        if self.abstract:
            arr = jax.ShapeDtypeStruct(shape, dtype)
        elif init == "zeros":
            arr = jnp.zeros(shape, dtype)
        elif init == "ones":
            arr = jnp.ones(shape, dtype)
        else:
            fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
            s = scale if scale is not None else 1.0 / math.sqrt(fan_in)
            arr = (jax.random.normal(self._next_key(), shape, jnp.float32)
                   * s).astype(dtype)
        node, snode = self.params, self.specs
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
            snode = snode.setdefault(p, {})
        node[parts[-1]] = arr
        snode[parts[-1]] = tuple(logical)

    def build(self) -> tuple[Params, Specs]:
        return self.params, self.specs


def tree_bytes(tree: Params) -> int:
    return sum(math.prod(l.shape) * np.dtype(l.dtype).itemsize
               for l in jax.tree.leaves(tree))


def cast_tree(tree: Params, dtype) -> Params:
    return jax.tree.map(lambda x: x.astype(dtype), tree)
