"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before first jax init).  Every axis
is `AxisType.Auto`: the compiler places the collectives."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axis_names):
    """`jax.make_mesh` over the process's devices, all axes Auto."""
    return jax.make_mesh(shape, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod meshes: (16, 16) single pod, (2, 16, 16) two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Mesh over whatever devices exist (CI/local): data × model."""
    n = len(jax.devices())
    assert n % model == 0, (n, model)
    return make_mesh((n // model, model), ("data", "model"))


_SHARED_MESHES: dict[int, object] = {}


def shared_host_mesh(model: int = 1):
    """The process-wide mesh the serving Gateway owns.

    Co-scheduled workloads (graph queries + LM decode) must share ONE
    device pool — two independently constructed meshes over the same
    devices would each believe they own the hardware.  This memoizes
    `make_host_mesh` per model-axis width so every Gateway tenant in a
    process resolves to the same Mesh object."""
    if model not in _SHARED_MESHES:
        _SHARED_MESHES[model] = make_host_mesh(model=model)
    return _SHARED_MESHES[model]


HW = {
    # TPU v5e per-chip numbers used for the roofline terms
    "peak_flops_bf16": 197e12,     # FLOP/s
    "hbm_bw": 819e9,               # B/s
    "ici_link_bw": 50e9,           # B/s per link (prompt-specified)
    "hbm_bytes": 16e9,
}
