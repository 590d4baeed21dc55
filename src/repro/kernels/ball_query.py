"""Block-wise ball-query Pallas kernel — RSPU grouping mode (paper §V-C).

A leaf's centers tile (its FPS samples) and search window (its parent
range, contiguous thanks to the DFT layout) are both VMEM-resident; every
center reuses the same window — the paper's intra-block data reuse (7.6x
memory-access reduction).

Centers come lane-major with their axis padded to R, a multiple of 8
sublanes (not to 128 lanes).  One grid step takes G leaves, G =
``leaves_per_step(R, NB)``: as many R-row leaves as fill a 128-row
distance tile (G = 1 once R > 64).  The distance tile is (G, R, W), each
leaf's rows against its own window, summed coordinate by coordinate, then
collapsed to (G·R, W) — free, since R % 8 == 0; neighbor selection is
repeated masked min over all G·R rows at once (the merge-sort top-k unit's
TPU analogue), so every row sees the arithmetic of a one-leaf step.  The
kernel also counts the in-radius candidates per center (the ASIC's
counter), so callers get ``cnt`` without a second pass; counts leave as an
(R, 1) column per leaf.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (INF, argmin_extract, leaves_per_step,
                                  pad_leaves, sqdist_cols)


def _bq_kernel(c_ref, cmask_ref, w_ref, wmask_ref, idx_ref, d2_ref, cnt_ref,
               *, num: int, r2: float):
    g, _, r = c_ref.shape
    c = jnp.swapaxes(c_ref[...], 1, 2)               # (G, R, 3)
    cm = jnp.swapaxes(cmask_ref[...], 1, 2) > 0      # (G, R, 1)
    wm = wmask_ref[...] > 0                          # (G, 1, W)
    d = jnp.where(wm, sqdist_cols(c, w_ref[...]), INF)   # (G, R, W)
    in_r = (d <= r2) & wm
    cnt = jnp.sum(in_r.astype(jnp.int32), axis=2, keepdims=True)
    cnt_ref[...] = jnp.where(cm, cnt, 0)
    idx, val = argmin_extract(d.reshape(g * r, d.shape[2]), num)
    idx_ref[...] = idx.reshape(g, r, num)
    d2_ref[...] = val.reshape(g, r, num)


@functools.partial(jax.jit,
                   static_argnames=("radius", "num", "interpret"))
def ball_query_blocks(centers: jax.Array, cmask: jax.Array, window: jax.Array,
                      wmask: jax.Array, *, radius: float, num: int,
                      interpret: bool):
    """centers (NB,3,R), cmask (NB,1,R), window (NB,3,W), wmask (NB,1,W),
    R a multiple of 8 -> (idx (NB,R,num) i32 local-to-window, d2 (NB,R,num),
    cnt (NB,R))."""
    nb, _, r = centers.shape
    w = window.shape[-1]
    g = leaves_per_step(r, nb)
    args = pad_leaves(g, *(a.astype(jnp.float32)
                           for a in (centers, cmask, window, wmask)))
    nbp = args[0].shape[0]
    kernel = functools.partial(_bq_kernel, num=num, r2=float(radius) ** 2)
    idx, d2, cnt = pl.pallas_call(
        kernel,
        grid=(nbp // g,),
        in_specs=[
            pl.BlockSpec((g, 3, r), lambda b: (b, 0, 0)),
            pl.BlockSpec((g, 1, r), lambda b: (b, 0, 0)),
            pl.BlockSpec((g, 3, w), lambda b: (b, 0, 0)),
            pl.BlockSpec((g, 1, w), lambda b: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((g, r, num), lambda b: (b, 0, 0)),
            pl.BlockSpec((g, r, num), lambda b: (b, 0, 0)),
            pl.BlockSpec((g, r, 1), lambda b: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nbp, r, num), jnp.int32),
            jax.ShapeDtypeStruct((nbp, r, num), jnp.float32),
            jax.ShapeDtypeStruct((nbp, r, 1), jnp.int32),
        ],
        interpret=interpret,
    )(*args)
    return idx[:nb], d2[:nb], cnt[:nb, :, 0]
