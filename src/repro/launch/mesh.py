"""Production mesh definitions (TPU v5e pods; see DESIGN.md §4).

Functions, not module-level constants, so importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# TPU v5e hardware constants (per chip) used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW = 50e9                   # B/s per link
CHIP_HBM_BYTES = 16 * 1024**3


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the engines and the
    zoo place data with ``NamedSharding``/``shard_map`` and let the
    partitioner propagate, which ``Explicit`` axes (JAX's default since
    0.7) reject on reshapes of sharded arrays."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips as (data=16, model=16).
    Multi-pod: 2 pods = 512 chips as (pod=2, data=16, model=16)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def mesh_chips(mesh) -> int:
    return mesh.devices.size


def make_superstep_mesh(num_devices: int | None = None):
    """1-D ``("data",)`` mesh for the sharded compiled superstep
    (DESIGN.md §8): the DL **node axis** is sharded over ``data``, so
    ``dlrt.distributed``'s node-axis heuristics (``node_axes`` /
    ``leaf_spec``) apply unchanged.

    ``num_devices=None`` uses every local device.  On CPU, simulate a
    multi-device host with ``XLA_FLAGS=--xla_force_host_platform_device_
    count=8`` (set before importing jax) — the conformance tests and
    ``benchmarks/fig10_sharded.py`` run exactly that way.
    """
    avail = jax.local_device_count()
    nd = avail if num_devices is None else num_devices
    if nd < 1 or nd > avail:
        raise ValueError(f"num_devices={nd} not in [1, {avail}] "
                         "(set XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N before importing jax to simulate "
                         "more CPU devices)")
    return _auto_mesh((nd,), ("data",))


def make_sweep_mesh(exp_devices: int, node_devices: int = 1):
    """2-D ``("exp", "data")`` mesh for the sweep engine
    (``repro.dlrt.SweepSuperstep``, DESIGN.md §14): the **experiment
    axis** shards over ``exp`` (embarrassingly parallel — every
    trajectory is independent, so the split is bitwise-free) and the DL
    **node axis** over ``data`` (the same gather-collective schedule the
    1-D sharded superstep uses).

    ``exp_devices * node_devices`` must not exceed the local device
    count; simulate a multi-device CPU host with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before
    importing jax), exactly like :func:`make_superstep_mesh`.
    """
    avail = jax.local_device_count()
    if exp_devices < 1 or node_devices < 1:
        raise ValueError("exp_devices and node_devices must be >= 1")
    if exp_devices * node_devices > avail:
        raise ValueError(
            f"exp_devices*node_devices={exp_devices * node_devices} > "
            f"{avail} local devices (set XLA_FLAGS=--xla_force_host_"
            "platform_device_count=N before importing jax to simulate "
            "more CPU devices)")
    return _auto_mesh((exp_devices, node_devices), ("exp", "data"))
