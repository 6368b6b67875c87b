"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (importing this module never
touches jax device state).  Single pod: 16x16 = 256 chips (v5e pod);
multi-pod: 2 pods = 512 chips with the ``pod`` axis outermost — only
data-parallel gradient reduction crosses it (DESIGN.md §6).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (tests, examples, elastic re-meshes).  Axes are
    ``Auto``: GSPMD propagates shardings from the ``with_sharding_constraint``
    hints the models place, as every caller here expects."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def pod_size(mesh) -> int:
    """Devices per pod (for DCI vs ICI classification in hw.hlo)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for name, s in sizes.items():
        if name != "pod":
            n *= s
    return n
