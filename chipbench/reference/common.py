"""Plain float32 pieces every family's reference shares: matmuls at
HIGHEST precision, the lower-precision control, the loss, AdamW, and the
weights made from a seed.

Nothing here imports the program.  The control (``control=True``) is the
same reference with every matmul operand rounded to float8 (e4m3, one
scale per tensor, gradients passed straight through): the step below the
bfloat16 that the configurations state.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


@jax.custom_vjp
def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(F8).astype(F32) * scale


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (g,)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def operand(x, control: bool):
    """A matmul operand: as it is, or rounded to the control's float8."""
    return _fp8(x) if control else x


def mm(eq: str, a, b, control: bool = False):
    """einsum in float32 at HIGHEST precision (the chip would otherwise
    take one bfloat16 pass)."""
    return jnp.einsum(eq, operand(a, control), operand(b, control),
                      precision=HIGHEST, preferred_element_type=F32)


def cross_entropy(logits, targets):
    """Mean next-token cross-entropy over every position."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    true = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - true)


# ---------------------------------------------------------------------------
# weights from a seed
# ---------------------------------------------------------------------------

def key_from_seed(seed: int):
    """A key that differs for every seed up to 2**64 (``PRNGKey`` keeps
    only the low 32 bits of a larger one)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def make_weights(layout: dict, key) -> dict:
    """Weights for ``layout`` (path -> (shape, init)), each leaf drawn
    from its own fold of ``key``.  An init is ``("normal", std)``,
    ``("uniform", lo, hi)``, ``("const", value)``, ``("one_plus_normal",
    std)`` or ``("decay_speed",)`` (RWKV6's per-layer ramp of w0)."""
    out: dict = {}
    for i, (path, (shape, init)) in enumerate(sorted(layout.items())):
        k = jax.random.fold_in(key, i)
        kind = init[0]
        if kind == "normal":
            arr = jax.random.normal(k, shape, F32) * init[1]
        elif kind == "one_plus_normal":
            arr = 1.0 + jax.random.normal(k, shape, F32) * init[1]
        elif kind == "uniform":
            arr = jax.random.uniform(k, shape, F32, init[1], init[2])
        elif kind == "const":
            arr = jnp.full(shape, init[1], F32)
        elif kind == "decay_speed":
            arr = _decay_speed(shape)
        else:
            raise ValueError(f"unknown init {init!r} for {path}")
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = arr
    return out


def _decay_speed(shape):
    """RWKV6's w0 at initialisation: for layer l of L and channel n of d,
    -6 + 5 (n/(d-1))^(0.7 + 1.3 l/(L-1))."""
    n_layers, d = shape
    n = jnp.arange(d, dtype=F32) / max(d - 1, 1)
    ratio = jnp.arange(n_layers, dtype=F32) / max(n_layers - 1, 1)
    return -6.0 + 5.0 * n[None, :] ** (0.7 + 1.3 * ratio[:, None])


def fan_in_std(shape) -> float:
    return 1.0 / math.sqrt(shape[-2])


# ---------------------------------------------------------------------------
# AdamW, as the configuration states it
# ---------------------------------------------------------------------------

def learning_rate(opt: dict, t):
    """lr at optimizer step ``t`` (1 for the first update): linear warm-up
    as min(1, (t+1)/warmup), then cosine down to min_lr_ratio x lr."""
    t = jnp.asarray(t, F32)
    warm = jnp.minimum(1.0, (t + 1.0) / opt["warmup_steps"])
    frac = jnp.clip((t - opt["warmup_steps"])
                    / max(opt["total_steps"] - opt["warmup_steps"], 1),
                    0.0, 1.0)
    decay = 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
    ratio = opt["min_lr_ratio"]
    return opt["lr"] * warm * (ratio + (1.0 - ratio) * decay)


def adamw(opt: dict, params, grads, mu, nu, t):
    """One AdamW update after clipping by the global norm; returns
    (params, mu, nu, the clipped gradients)."""
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    scale = jnp.minimum(1.0, opt["grad_clip_norm"] / jnp.maximum(gnorm,
                                                                 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2 = opt["b1"], opt["b2"]
    tf = jnp.asarray(t, F32)
    bc1 = 1.0 - b1 ** tf
    bc2 = 1.0 - b2 ** tf
    lr = learning_rate(opt, t)
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)

    def upd(p, m, v):
        delta = (m / bc1) / (jnp.sqrt(v / bc2) + opt["eps"])
        return p - lr * (delta + opt["weight_decay"] * p)

    params = jax.tree.map(upd, params, mu, nu)
    return params, mu, nu, grads


def leaf_norms(tree) -> dict:
    """Euclidean norm of every leaf, and of every layer of a stacked leaf
    (those under ``layers/``): path -> () or (n_layers,) array."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        x = x.astype(F32)
        if name.startswith("layers/"):
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x),
                                         axis=tuple(range(1, x.ndim))))
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x)))
    return out
