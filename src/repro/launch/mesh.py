"""Production meshes (system prompt MULTI-POD DRY-RUN step 1).

Functions, not module constants, so importing never touches jax device
state.  Axis semantics:
  pod    — gradient all-reduce across pods (pure DP)
  data   — batch sharding + FSDP (ZeRO-3) parameter/optimizer sharding
  model  — tensor/expert/sequence parallel axis
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_host_mesh(shape, axes)


def make_host_mesh(shape=None, axes=("data", "model")):
    """Mesh over whatever devices exist (tests / smoke runs), every axis
    Auto; ``shape`` defaults to all devices on the first axis."""
    n = len(jax.devices())
    if shape is None:
        shape = (n, 1) if len(axes) == 2 else (n,)
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))
