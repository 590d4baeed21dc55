"""Block-wise k-NN Pallas kernel — RSPU interpolation mode (paper §V-C).

Same VMEM-resident window structure as ball query, without the radius
constraint: used for the 3-NN search of block-wise interpolation (BWI).
Queries here are *all* points of a fine leaf; candidates are the coarse
samples of the leaf's parent subtree.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import INF, argmin_extract, sqdist_cols


def _knn_kernel(q_ref, w_ref, wmask_ref, idx_ref, d2_ref, *, k: int):
    q = q_ref[0].T               # (Q, 3)
    w = w_ref[0]                 # (3, W)
    wm = wmask_ref[0] > 0        # (1, W)
    d = jnp.where(wm, sqdist_cols(q, w), INF)       # (Q, W)
    idx, val = argmin_extract(d, k)
    idx_ref[0] = idx
    d2_ref[0] = val


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def knn_blocks(queries: jax.Array, window: jax.Array, wmask: jax.Array, *,
               k: int, interpret: bool):
    """queries (NB,3,Q), window (NB,3,W), wmask (NB,1,W)
    -> (idx (NB,Q,k) i32 local-to-window, d2 (NB,Q,k))."""
    nb, _, q = queries.shape
    w = window.shape[-1]
    kernel = functools.partial(_knn_kernel, k=k)
    return pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, 3, q), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 3, w), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, w), lambda b: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, q, k), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, q, k), lambda b: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, q, k), jnp.int32),
            jax.ShapeDtypeStruct((nb, q, k), jnp.float32),
        ],
        interpret=interpret,
    )(queries.astype(jnp.float32), window.astype(jnp.float32),
      wmask.astype(jnp.float32))
