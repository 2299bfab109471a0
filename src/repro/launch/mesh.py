"""Production meshes.

Single pod: 16 x 16 = 256 chips (``data`` x ``model``).
Multi-pod:  2 x 16 x 16 = 512 chips (``pod`` x ``data`` x ``model``) — the
``pod`` axis carries only data parallelism (gradient all-reduce crosses the
DCN/ICI pod boundary; everything bandwidth-hungry stays intra-pod).

Functions, not module constants: importing this module must never touch jax
device state (smoke tests run on 1 CPU device; only dryrun.py forces 512).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes, *, devices=None) -> Mesh:
    """Every mesh of the repo is built here, with all axes Auto: the
    compiler propagates shardings from the ``with_sharding_constraint``
    rules and ``shard_map`` regions, which is what every sharded path in
    the repo is written for (``jax.make_mesh`` defaults to Explicit axes,
    under which those rules raise)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests, examples)."""
    return make_mesh((data, model), ("data", "model"))


# TPU v5e hardware constants (roofline denominators; consumed by
# repro/roofline/analysis.py and benchmarks/roofline_report.py).
PEAK_FLOPS_BF16 = 197e12     # per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link
