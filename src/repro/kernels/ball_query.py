"""Block-wise ball-query Pallas kernel — RSPU grouping mode (paper §V-C).

One grid step = one leaf: the centers tile (the leaf's FPS samples) and the
search window (the leaf's parent range, contiguous thanks to the DFT layout)
are both VMEM-resident; every center reuses the same window — the paper's
intra-block data reuse (7.6x memory-access reduction).

The distance matrix is summed coordinate by coordinate over a (KC, W) tile
(centers turned into columns once per block); neighbor selection is
repeated masked min (the merge-sort top-k unit's TPU analogue).  The kernel
also counts the in-radius candidates per center (the ASIC's counter), so
callers get ``cnt`` without a second pass; counts leave as a (KC, 1)
column per block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import INF, argmin_extract, sqdist_cols


def _bq_kernel(c_ref, cmask_ref, w_ref, wmask_ref, idx_ref, d2_ref, cnt_ref,
               *, num: int, r2: float):
    c = c_ref[0].T                  # (KC, 3)
    cm = cmask_ref[0].T > 0         # (KC, 1)
    w = w_ref[0]                    # (3, W)
    wm = wmask_ref[0] > 0           # (1, W)
    d = jnp.where(wm, sqdist_cols(c, w), INF)       # (KC, W)
    in_r = (d <= r2) & wm
    cnt = jnp.sum(in_r.astype(jnp.int32), axis=1, keepdims=True)
    cnt_ref[0] = jnp.where(cm, cnt, 0)
    idx, val = argmin_extract(d, num)
    idx_ref[0] = idx
    d2_ref[0] = val


@functools.partial(jax.jit,
                   static_argnames=("radius", "num", "interpret"))
def ball_query_blocks(centers: jax.Array, cmask: jax.Array, window: jax.Array,
                      wmask: jax.Array, *, radius: float, num: int,
                      interpret: bool):
    """centers (NB,3,KC), cmask (NB,1,KC), window (NB,3,W), wmask (NB,1,W)
    -> (idx (NB,KC,num) i32 local-to-window, d2 (NB,KC,num), cnt (NB,KC))."""
    nb, _, kc = centers.shape
    w = window.shape[-1]
    r2 = float(radius) ** 2
    kernel = functools.partial(_bq_kernel, num=num, r2=r2)
    idx, d2, cnt = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, 3, kc), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, kc), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 3, w), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, w), lambda b: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, kc, num), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, kc, num), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, kc, 1), lambda b: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, kc, num), jnp.int32),
            jax.ShapeDtypeStruct((nb, kc, num), jnp.float32),
            jax.ShapeDtypeStruct((nb, kc, 1), jnp.int32),
        ],
        interpret=interpret,
    )(centers.astype(jnp.float32), cmask.astype(jnp.float32),
      window.astype(jnp.float32), wmask.astype(jnp.float32))
    return idx, d2, cnt[..., 0]
