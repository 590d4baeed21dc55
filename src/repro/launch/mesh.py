"""Production mesh definitions (TPU v5e pods).

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state): single-pod (16, 16) ("data", "model") = v5e-256; with
``multi_pod=True`` (2, 16, 16) ("pod", "data", "model") = 2 pods / 512
chips.  Elastic variants live in repro/dist/elastic.py.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))

