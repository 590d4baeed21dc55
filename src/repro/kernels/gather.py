"""Block-wise gathering Pallas kernel (paper BWGa, §IV-B / §V-B).

The ASIC insight: after Fractal, each gather unit only touches one parent
window, which fits on-chip — no global random access.  The TPU analogue:
the window's features are one VMEM tile per grid step, and the *random*
in-window gather becomes a one-hot (M, W) x (W, C) matmul on the MXU —
random access converted to dense compute, the canonical TPU trade.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


def _gather_kernel(feats_ref, idx_ref, out_ref):
    f = feats_ref[0]             # (W, C)
    idx = idx_ref[0]             # (M, 1) i32
    w = f.shape[0]
    m = idx.shape[0]
    iot = lax.broadcasted_iota(jnp.int32, (m, w), 1)
    onehot = (iot == idx).astype(f.dtype)
    # HIGHEST: a one-hot fetch must return the features exactly, not
    # rounded through a single bf16 MXU pass.
    out_ref[0] = jnp.dot(onehot, f, preferred_element_type=f.dtype,
                         precision=lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_blocks(window_feats: jax.Array, idx: jax.Array, *,
                  interpret: bool) -> jax.Array:
    """window_feats (NB, W, C), idx (NB, M) local-to-window
    -> (NB, M, C) gathered features.  Out-of-range idx (negative or >= W)
    matches no one-hot row and fetches zeros."""
    nb, w, c = window_feats.shape
    m = idx.shape[-1]
    return pl.pallas_call(
        _gather_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, w, c), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, m, 1), lambda b: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, m, c), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, m, c), window_feats.dtype),
        interpret=interpret,
    )(window_feats, idx.astype(jnp.int32)[:, :, None])


def _scatter_add_kernel(g_ref, idx_ref, out_ref):
    g = g_ref[0]                 # (M, C) cotangent rows
    idx = idx_ref[0]             # (1, M) i32
    w = out_ref.shape[-2]
    m = g.shape[0]
    # Transpose of the forward's one-hot: (W, M) @ (M, C) on the MXU.
    # Out-of-range idx (including the -1 lane padding) matches no row and
    # contributes nothing — the scatter drops exactly what the gather
    # zero-filled.
    iot = lax.broadcasted_iota(jnp.int32, (w, m), 0)
    onehot_t = (iot == idx).astype(g.dtype)
    out_ref[0] = jnp.dot(onehot_t, g, preferred_element_type=g.dtype,
                         precision=lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("w", "interpret"))
def scatter_add_blocks(g: jax.Array, idx: jax.Array, *, w: int,
                       interpret: bool) -> jax.Array:
    """gather_blocks' backward: g (NB, M, C) cotangents, idx (NB, M)
    local-to-window -> (NB, W, C) scatter-added window cotangents.

    The ASIC story holds in reverse: each block's backward touches only
    its own VMEM-resident window tile, and the random scatter-add becomes
    a dense (W, M) x (M, C) matmul — the forward's one-hot trick,
    transposed (docs/DESIGN.md §4)."""
    nb, m, c = g.shape
    return pl.pallas_call(
        _scatter_add_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, m, c), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, m), lambda b: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, w, c), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, w, c), g.dtype),
        interpret=interpret,
    )(g, idx.astype(jnp.int32)[:, None, :])
