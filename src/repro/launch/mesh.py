"""Mesh construction. Functions, not constants: importing this module never
touches jax device state.

Every mesh in the repository is built here with ``Auto`` axes:
``Sharder`` places activations with ``with_sharding_constraint``, which
accepts only Auto axes, while ``jax.make_mesh`` defaults to Explicit ones.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.configs.base import (MULTI_POD_MESH, SINGLE_POD_MESH, SMOKE_MESH,
                                MeshConfig)


def make_production_mesh(*, multi_pod: bool = False):
    """The target deployment mesh: 16x16 (one v5e pod, 256 chips) or
    2x16x16 (two pods, 512 chips, 'pod' axis over DCN)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def auto_mesh(shape, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis Auto (see the module docstring)."""
    return jax.make_mesh(tuple(shape), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def make_mesh(cfg: MeshConfig, *, devices=None):
    return auto_mesh(cfg.shape, cfg.axis_names, devices=devices)


def make_smoke_mesh():
    """1x1 mesh over the single local device (smoke tests / examples)."""
    return make_mesh(SMOKE_MESH)


def mesh_config_for(mesh) -> MeshConfig:
    names = tuple(mesh.axis_names)
    if names == ("pod", "data", "model"):
        return MULTI_POD_MESH
    if names == ("data", "model"):
        if tuple(mesh.devices.shape) == (16, 16):
            return SINGLE_POD_MESH
        return MeshConfig(shape=tuple(mesh.devices.shape), axis_names=names)
    return MeshConfig(shape=tuple(mesh.devices.shape), axis_names=names)
