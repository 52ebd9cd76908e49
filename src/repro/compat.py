"""The one JAX spelling this repo pins: all-Auto mesh axes.

The installed JAX (0.9.x) has ``jax.shard_map``, ``jax.lax.axis_size``
and a dict-valued ``Compiled.cost_analysis()``, so the code calls those
directly.  What it changed is the default of ``jax.make_mesh``: its axes
are now ``AxisType.Explicit``, under which sharding-in-types rejects
gathers and contractions that GSPMD partitions by itself.  Every mesh in
this repo wants all-Auto axes, so ``make_mesh`` asks for them.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names))
