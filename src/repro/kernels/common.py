"""Shared helpers for the FractalCloud Pallas TPU kernels.

TPU notes (kernels compile for TPU v5e and run in interpret mode on CPU):

* every value stays 2-D — ``(1, L)`` rows, ``(R, 1)`` columns, ``(R, L)``
  tiles — with the large axis last so it lands on the 128-wide lane
  dimension; reductions keep their dims, and no scalar ever leaves the
  vector unit;
* arg-extrema are a max/min reduction followed by a min over the lanes
  that attain it (the first extremum, as ``jnp.argmax``/``argmin`` pick);
* dynamic gathers inside VMEM are one-hot selects/matmuls (iota == idx),
  and per-slot outputs are accumulated with selects and stored once;
* loop counts (k, num) are static and small, so selection loops unroll;
* the neighbour searches (ball query, kNN) pack the query rows of several
  leaves into one grid step's 128-row distance tile (``leaves_per_step``).
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

# Plain Python floats: Pallas kernel bodies may not capture device constants.
NEG = -3.0e38
INF = 3.0e38
TILE_ROWS = 128     # rows of a neighbour search's distance tile per grid step


def first_lane(hit, iot, n: int):
    """Per row, the first lane where ``hit`` holds (``n`` if none) as an
    ``(R, 1)`` column."""
    return jnp.min(jnp.where(hit, iot, n), axis=-1, keepdims=True)


def sqdist_cols(a, b):
    """a (..., r, 3) point rows, b (..., 3, n) lane-major points ->
    (..., r, n) squared distances, summed coordinate by coordinate (no
    cancellation, no MXU rounding; ``kernels/ref.py`` evaluates the same
    sum in the same order)."""
    d = None
    for k in range(3):
        diff = a[..., k:k + 1] - b[..., k:k + 1, :]
        d = diff * diff if d is None else d + diff * diff
    return d


def leaves_per_step(rows: int, nb: int) -> int:
    """Leaves that share one neighbour-search grid step: as many blocks of
    ``rows`` query rows (a multiple of 8) as fill a ``TILE_ROWS``-row
    distance tile, at most ``nb`` and at least one."""
    return max(1, min(TILE_ROWS // rows, nb))


def pad_leaves(g: int, *arrays):
    """Pad the leading (leaf) axis of each array to a multiple of ``g`` with
    zeros: the padded leaves' masks are 0, so they select nothing."""
    pad = (-arrays[0].shape[0]) % g
    return tuple(jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                 for a in arrays)


def argmin_extract(d, num: int):
    """d (r, n): indices/values of the num smallest per row by repeated
    masked min (the TPU analogue of the paper's merge-sort top-k unit).
    Returns (idx (r, num) i32, val (r, num))."""
    r, n = d.shape
    iot = lax.broadcasted_iota(jnp.int32, (r, n), 1)
    slot = lax.broadcasted_iota(jnp.int32, (r, num), 1)
    idx = jnp.zeros((r, num), jnp.int32)
    val = jnp.zeros((r, num), d.dtype)
    for j in range(num):
        v = jnp.min(d, axis=1, keepdims=True)
        i = first_lane(d == v, iot, n)
        idx = jnp.where(slot == j, i, idx)
        val = jnp.where(slot == j, v, val)
        d = jnp.where(iot == i, INF, d)
    return idx, val
