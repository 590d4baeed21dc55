"""Fractal-engine Pallas kernel — the pipelined partition step (paper §V-B).

Paper Fig. 9(b,c): the partition unit and the midpoint-computation unit run
*pipelined* — iteration l partitions on dimension d using the mid computed
one iteration earlier, while simultaneously computing the children's
min/max on dimension d+1.  This kernel fuses exactly those two stages into
one linear VMEM pass per node:

  inputs : node coords (3, BS), validity, this node's split value `mid`
  outputs: side bits, left count (the ASIC counter), and the four child
           extrema on the *next* dimension (lmin, lmax, rmin, rmax) from
           which the host derives both children's mids with one add+shift
           (min-max averaging, paper §V-B) — no second traversal.

Every per-node array carries a unit middle axis, so each block's last two
dims equal the array's (the TPU block rule) and scalars stay (1, 1)
vectors.  The layout scatter (prefix-sum destinations) stays in XLA: it is
a permutation, not a traversal, and XLA already streams it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.common import INF, NEG


def _level_kernel(coords_ref, vmask_ref, mid_ref, side_ref, lcnt_ref,
                  stats_ref, *, da: int, db: int):
    c = coords_ref[0]            # (3, BS)
    v = vmask_ref[0] > 0         # (1, BS)
    mid = mid_ref[0]             # (1, 1)
    xa = c[da:da + 1, :]
    xb = c[db:db + 1, :]
    side = (xa > mid) & v
    side_ref[0] = side.astype(jnp.int32)
    left = v & ~side
    lcnt_ref[0] = jnp.sum(left.astype(jnp.int32), axis=1, keepdims=True)
    ext = (jnp.min(jnp.where(left, xb, INF), axis=1, keepdims=True),
           jnp.max(jnp.where(left, xb, NEG), axis=1, keepdims=True),
           jnp.min(jnp.where(side, xb, INF), axis=1, keepdims=True),
           jnp.max(jnp.where(side, xb, NEG), axis=1, keepdims=True))
    slot = lax.broadcasted_iota(jnp.int32, (1, 4), 1)
    stats = jnp.zeros((1, 4), jnp.float32)
    for j, e in enumerate(ext):
        stats = jnp.where(slot == j, e, stats)
    stats_ref[0] = stats


@functools.partial(jax.jit, static_argnames=("da", "db", "interpret"))
def fractal_level_blocks(coords: jax.Array, vmask: jax.Array,
                         mid: jax.Array, *, da: int, db: int,
                         interpret: bool):
    """coords (NB,3,BS), vmask (NB,1,BS), mid (NB,1) ->
    (side (NB,BS) i32, left_count (NB,) i32, child_stats (NB,4) f32
     = [lmin_b, lmax_b, rmin_b, rmax_b])."""
    nb, _, bs = coords.shape
    kernel = functools.partial(_level_kernel, da=da, db=db)
    side, lcnt, stats = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, 3, bs), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, bs), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bs), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, 4), lambda b: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, 1, bs), jnp.int32),
            jax.ShapeDtypeStruct((nb, 1, 1), jnp.int32),
            jax.ShapeDtypeStruct((nb, 1, 4), jnp.float32),
        ],
        interpret=interpret,
    )(coords.astype(jnp.float32), vmask.astype(jnp.float32),
      mid.astype(jnp.float32)[:, :, None])
    return side[:, 0], lcnt[:, 0, 0], stats[:, 0]
