"""Plain float32 references, one module per family, and the training run
that the check compares with: the first steps of AdamW from the seed's
weights on the traffic's first batches.

Nothing here imports the program.  ``run`` places the reference on every
device it is given, each parameter split along its largest axis that the
device count divides, so that a model that needs several chips to train
fits them.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.common import adamw, leaf_norms, make_weights


def family(name: str):
    return importlib.import_module(f"chipbench.reference.{name}")


def _split_largest(shape, n: int, mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P
    parts = [None] * len(shape)
    for axis in sorted(range(len(shape)), key=lambda a: -shape[a]):
        if shape[axis] % n == 0:
            parts[axis] = "all"
            break
    return NamedSharding(mesh, P(*parts))


def placement(layout: dict, devices) -> tuple:
    """(parameter shardings, batch sharding), or (None, None) on one
    device."""
    if len(devices) == 1:
        return None, None
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(devices), ("all",))
    tree: dict = {}
    for path, (shape, _) in layout.items():
        node = tree
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = _split_largest(shape, len(devices), mesh)
    return tree, NamedSharding(mesh, P("all", None))


def run(c: dict, opt: dict, key, batches: list[dict], devices,
        control: bool = False) -> dict:
    """Train ``len(batches)`` steps from ``make_weights(layout, key)``.

    Returns each step's loss, the per-leaf norms of the first step's
    clipped gradient, and of the change of the parameters over all
    steps."""
    if control and c["compute_dtype"] != "bfloat16":
        raise ValueError("the float8 control stands below bfloat16, not "
                         f"below {c['compute_dtype']}")
    mod = family(c["family"])
    layout = mod.layout(c)
    p_sh, b_sh = placement(layout, devices)
    init = jax.jit(lambda k: make_weights(layout, k), out_shardings=p_sh)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                    out_shardings=p_sh)

    def step(params, mu, nu, t, tokens, targets):
        loss, grads = jax.value_and_grad(mod.loss, argnums=1)(
            c, params, tokens, targets, control)
        params, mu, nu, clipped = adamw(opt, params, grads, mu, nu, t)
        return params, mu, nu, loss, leaf_norms(clipped)

    step = jax.jit(step, donate_argnums=(0, 1, 2))
    change = jax.jit(lambda p, k: leaf_norms(jax.tree.map(
        jnp.subtract, p, make_weights(layout, k))))

    params = init(key)
    mu, nu = zeros(params), zeros(params)
    losses, grad_norms = [], None
    for t, batch in enumerate(batches, start=1):
        tok, tgt = (jax.device_put(batch[k], b_sh)
                    for k in ("tokens", "targets"))
        params, mu, nu, loss, gn = step(params, mu, nu, t, tok, tgt)
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = jax.device_get(gn)
    del mu, nu
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": jax.device_get(change(params, key))}
