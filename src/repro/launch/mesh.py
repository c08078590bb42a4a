"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — smoke tests see 1 device; only dryrun.py sets
``xla_force_host_platform_device_count=512`` before first jax init.

Every mesh is built with ``AxisType.Auto`` axes: the partition rules in
``repro.dist.sharding`` place arrays with ``NamedSharding`` /
``with_sharding_constraint`` hints and leave the rest to the SPMD
partitioner. ``jax.make_mesh``'s own default (``Explicit`` axes) would make
every op resolve its output sharding from its operands, which the
embedding gather and the packed-codes unpack chain cannot do unambiguously.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, names):
    """``jax.make_mesh`` over all present devices, with Auto axes."""
    return jax.make_mesh(tuple(shape), tuple(names),
                         axis_types=(AxisType.Auto,) * len(names))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = one 256-chip v5e pod; 2x16x16 = two pods (512 chips).

    Axes: ('data', 'model') single-pod; ('pod', 'data', 'model') multi-pod.
    pod x data is pure data-parallel (the gradient all-reduce over the
    combined axes is hierarchical by construction: XLA emits the reduce over
    the product group, intra-pod ICI first, cross-pod DCN once per step);
    'model' is megatron tensor parallel.
    """
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def make_mesh_by_name(name: str):
    if name in ("single", "single_pod", "pod", "16x16"):
        return make_production_mesh(multi_pod=False), "16x16"
    if name in ("multi", "multi_pod", "2x16x16"):
        return make_production_mesh(multi_pod=True), "2x16x16"
    if name in ("host", "cpu", "1"):
        return make_mesh((1,), ("data",)), "1"
    if name in ("host8", "2x4"):
        # 8 forced host devices (xla_force_host_platform_device_count=8):
        # 2-way data (engine slot axis) x 4-way megatron tensor parallel —
        # the serve-smoke / multi-device test topology
        return make_mesh((2, 4), ("data", "model")), "2x4"
    if name == "1x4":
        # four present devices (one v5e host's 2x2 chips, or 4 forced host
        # devices): 4-way megatron tensor parallel, no data split
        return make_mesh((1, 4), ("data", "model")), "1x4"
    raise ValueError(f"unknown mesh {name!r}")
