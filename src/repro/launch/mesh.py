"""Production mesh builders.

make_production_mesh is a FUNCTION (not module-level state) so importing this
module never touches jax device initialization — only dryrun.py (which sets
XLA_FLAGS first) materializes the 512-way host-device mesh.
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_local_mesh", "parse_mesh_spec"]


def _make_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod meshes: (16,16)=256 chips single-pod; (2,16,16)=512 two-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU demos)."""
    return _make_mesh((data, model), ("data", "model"))


def parse_mesh_spec(spec: str):
    """Build a (data, model) mesh from a CLI string like ``data=2,model=4``.

    Each comma-separated entry is ``axis`` or ``axis=N`` with axis in
    {data, model}.  The FIRST entry without ``=N`` absorbs every device the
    other axes leave over; further bare entries get size 1 — so on 8
    devices ``data,model=2`` is 4x2, ``data,model`` is 8x1.  Unnamed axes
    get size 1.  Raises ValueError for unknown axes, duplicate entries,
    non-positive sizes, or a layout that does not fit the device count.
    """
    sizes: dict = {}
    wildcard = None
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry:
            continue
        name, _, num = entry.partition("=")
        name = name.strip()
        if name not in ("data", "model"):
            raise ValueError(f"unknown mesh axis {name!r} (want data/model)")
        if name in sizes or name == wildcard:
            raise ValueError(f"duplicate mesh axis {name!r}")
        if num:
            sizes[name] = int(num)
            if sizes[name] < 1:
                raise ValueError(f"mesh axis {name!r} must be >= 1: {num}")
        elif wildcard is None:
            wildcard = name
        else:
            sizes[name] = 1
    n_dev = len(jax.devices())
    explicit = 1
    for s in sizes.values():
        explicit *= s
    if wildcard is not None:
        if n_dev % explicit:
            raise ValueError(
                f"{explicit} explicit-axis devices do not divide {n_dev}"
            )
        sizes[wildcard] = n_dev // explicit
    total = sizes.get("data", 1) * sizes.get("model", 1)
    if total > n_dev:
        raise ValueError(f"mesh needs {total} devices, only {n_dev} present")
    return make_local_mesh(sizes.get("data", 1), sizes.get("model", 1))
