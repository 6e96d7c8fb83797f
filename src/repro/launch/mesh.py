"""Production meshes.

    single pod : (16, 16)      axes ("data", "model")       256 chips
    multi-pod  : (2, 16, 16)   axes ("pod", "data", "model") 512 chips

The model axis doubles as the Swapped Dragonfly: ``dragonfly_for_mesh``
views it as D3(K, M) (16 -> D3(4,2), so a pod's model axis runs the §3
all-to-all in K·M²/s ppermute rounds), and ``make_dragonfly_mesh`` builds a
flat 1-D mesh whose device order IS the router order — the executable form
of the core Schedule IR: ``runtime.lowering.lower`` emits one
``CollectiveProgram`` per schedule and ``dragonfly_runtime_backend``
returns the backend that replays it on the mesh.

Functions, not module constants — importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax import)."""

from __future__ import annotations

import jax

from repro.dist.mesh import DeviceLayout, dragonfly_layout
from repro.dist.sharding import ShardRules


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_rules(*, multi_pod: bool = False, fsdp: bool = False) -> ShardRules:
    return ShardRules(
        tensor_axis="model",
        data_axis="data",
        pod_axis="pod" if multi_pod else None,
        fsdp=fsdp,
    )


def axis_sizes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def dragonfly_for_mesh(mesh, axis: str = "model") -> DeviceLayout:
    """The D3 view of one mesh axis — what the dragonfly collectives
    (dist/collectives.py) replay their lowered schedules over."""
    return dragonfly_layout(axis_sizes(mesh)[axis])


def dragonfly_runtime_backend(name: str = "jax_ppermute", *, overlap: bool = False):
    """The runtime backend production launchers replay programs with.
    ``overlap=True`` orders stages by ``start_step`` so pipelined rounds
    interleave on the wire; ``name="reference"`` gives the device-free
    NumPy replay (host-side validation of a pod's schedules)."""
    from repro.runtime.backends import get_backend

    kwargs = {"overlap": overlap} if name in ("jax", "jax_ppermute") else {}
    return get_backend(name, **kwargs)


def make_dragonfly_mesh(n: int | None = None, axis_name: str = "df"):
    """A flat 1-D mesh over n devices in router order, plus its layout.

    Device i of the axis is router ``layout.topo.id_router(i)``; programs
    lowered from the IR (runtime/lowering.py) execute on it verbatim."""
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    n = n if n is not None else len(devs)
    if len(devs) < n:
        raise RuntimeError(f"need {n} devices, have {len(devs)}")
    return Mesh(np.array(devs[:n]), (axis_name,)), dragonfly_layout(n)
