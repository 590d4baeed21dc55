"""Block-wise k-NN Pallas kernel — RSPU interpolation mode (paper §V-C).

Same VMEM-resident window structure as ball query, without the radius
constraint: used for the 3-NN search of block-wise interpolation (BWI).
Queries here are *all* points of a fine leaf; candidates are the coarse
samples of the leaf's parent subtree.  As in ball query, the query axis is
padded to R, a multiple of 8 sublanes, and G = ``leaves_per_step(R, NB)``
leaves share a grid step's (G·R, W) distance tile (R 32 at th 32: G 4).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (INF, argmin_extract, leaves_per_step,
                                  pad_leaves, sqdist_cols)


def _knn_kernel(q_ref, w_ref, wmask_ref, idx_ref, d2_ref, *, k: int):
    g, _, r = q_ref.shape
    q = jnp.swapaxes(q_ref[...], 1, 2)               # (G, R, 3)
    wm = wmask_ref[...] > 0                          # (G, 1, W)
    d = jnp.where(wm, sqdist_cols(q, w_ref[...]), INF)   # (G, R, W)
    idx, val = argmin_extract(d.reshape(g * r, d.shape[2]), k)
    idx_ref[...] = idx.reshape(g, r, k)
    d2_ref[...] = val.reshape(g, r, k)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def knn_blocks(queries: jax.Array, window: jax.Array, wmask: jax.Array, *,
               k: int, interpret: bool):
    """queries (NB,3,R), window (NB,3,W), wmask (NB,1,W), R a multiple of 8
    -> (idx (NB,R,k) i32 local-to-window, d2 (NB,R,k))."""
    nb, _, r = queries.shape
    w = window.shape[-1]
    g = leaves_per_step(r, nb)
    args = pad_leaves(g, *(a.astype(jnp.float32)
                           for a in (queries, window, wmask)))
    nbp = args[0].shape[0]
    idx, d2 = pl.pallas_call(
        functools.partial(_knn_kernel, k=k),
        grid=(nbp // g,),
        in_specs=[
            pl.BlockSpec((g, 3, r), lambda b: (b, 0, 0)),
            pl.BlockSpec((g, 3, w), lambda b: (b, 0, 0)),
            pl.BlockSpec((g, 1, w), lambda b: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((g, r, k), lambda b: (b, 0, 0)),
            pl.BlockSpec((g, r, k), lambda b: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nbp, r, k), jnp.int32),
            jax.ShapeDtypeStruct((nbp, r, k), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return idx[:nb], d2[:nb]
