"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init,
while smoke tests and benches see 1 device.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """The one device-mesh constructor of the program.

    Every axis is ``AxisType.Auto``: the sharding model is GSPMD with
    constraints (``numerics.constrain``) and shard_map'd programs, which
    ``Explicit`` axes (``jax.make_mesh``'s default) refuse."""
    shape, axes = tuple(int(s) for s in shape), tuple(axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_abstract_mesh(axis_sizes, axis_names):
    """Device-free mesh for spec derivation (sharding rules, dry-run
    lowering), which needs only shape and names."""
    from jax.sharding import AbstractMesh

    axis_sizes = tuple(int(s) for s in axis_sizes)
    axis_names = tuple(axis_names)
    if len(axis_sizes) != len(axis_names):
        raise ValueError(f"{len(axis_sizes)} sizes vs {len(axis_names)} names")
    return AbstractMesh(axis_sizes, axis_names)


def make_host_mesh(shape=None, axes=("data", "model")):
    """Mesh over whatever devices exist (tests / local runs)."""
    n = jax.device_count()
    if shape is None:
        shape = (n, 1) if len(axes) == 2 else (n,)
    return make_mesh(shape, axes)


def data_axes(mesh) -> tuple:
    """Axes carrying batch data-parallelism (pod included when present)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def mesh_devices(mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))


# ---------------------------------------------------------------------------
# compression meshes (DESIGN.md §6)
# ---------------------------------------------------------------------------

def parse_mesh_spec(spec: str):
    """Parse a ``--mesh`` CLI spec into (shape, axes).

    Accepted forms: ``"data=4"``, ``"data=2,model=2"``, ``"4"`` (all-data),
    ``"4x2"`` (data x model). Axis names must come from
    {pod, data, model} so the existing sharding rules apply unchanged."""
    spec = spec.strip()
    known = ("pod", "data", "model")
    if "=" in spec:
        shape, axes = [], []
        for part in spec.split(","):
            name, _, size = part.partition("=")
            name = name.strip()
            if name not in known:
                raise ValueError(f"unknown mesh axis {name!r}; one of {known}")
            axes.append(name)
            shape.append(int(size))
        return tuple(shape), tuple(axes)
    sizes = tuple(int(s) for s in spec.replace("x", " ").split())
    if len(sizes) == 1:
        return sizes, ("data",)
    if len(sizes) == 2:
        return sizes, ("data", "model")
    raise ValueError(f"cannot parse mesh spec {spec!r}")


def make_compression_mesh(spec: str | None = None):
    """Mesh for the compression pipeline over the host's devices.

    Default: every device on the "data" axis (calibration capture is pure
    data-parallelism; the "model" axis only shards the solve stage)."""
    if spec is None:
        return make_mesh((jax.device_count(),), ("data",))
    return make_mesh(*parse_mesh_spec(spec))


def mesh_shape_dict(mesh) -> dict:
    """{axis: size} — the JSON-able mesh record plans/artifacts carry."""
    return {str(k): int(v) for k, v in mesh.shape.items()}


def expert_axis_size(mesh) -> int:
    """Size of the expert-parallel ("model") axis — the number of shards the
    per-expert compression solves split across (DESIGN.md §6)."""
    return int(mesh.shape.get("model", 1))
