"""Thin wrappers over JAX APIs the distribution layer and kernels share.

Import ``AxisType``, ``make_mesh``, ``shard_map`` and
``tpu_compiler_params`` from here, so a future API move is one edit.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names, *, axis_types=None, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` unless told otherwise."""
    if axis_types is None:
        axis_types = (AxisType.Auto,) * len(tuple(axis_names))
    kwargs = {"devices": devices} if devices is not None else {}
    return jax.make_mesh(axis_shapes, axis_names, axis_types=axis_types,
                         **kwargs)


def tpu_compiler_params(*, dimension_semantics=None, **kwargs):
    """Mosaic compiler params.  Annotates pallas grids with
    ``dimension_semantics`` ('parallel' axes may be split across
    TensorCores; 'arbitrary' axes are sequential revisits, e.g.
    accumulation over feature chunks)."""
    from jax.experimental.pallas import tpu as pltpu

    if dimension_semantics is not None:
        kwargs["dimension_semantics"] = tuple(dimension_semantics)
    return pltpu.CompilerParams(**kwargs)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=True):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
