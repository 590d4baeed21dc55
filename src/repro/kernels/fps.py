"""Block-parallel FPS Pallas kernel — the RSPU sampling mode (paper §V-C).

One grid step = one Fractal leaf (the paper's inter-block parallelism): the
block's coordinates live in VMEM for the whole FPS loop, and the running
min-distance vector, the last selection and the selected indices are the
loop carry.  The ASIC's window-check skip is realized as masking (visited
lanes pinned to -inf; see docs/DESIGN.md §2).

Layout: coords are (NB, 3, BS) so the point axis is the 128-lane axis; the
output is (NB, 1, k) so each block's (1, k) tile equals the array's last
two dims (TPU block rule).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.common import NEG, first_lane


def _fps_kernel(coords_ref, vmask_ref, idx_ref, *, k: int):
    c = coords_ref[0]          # (3, BS)
    v = vmask_ref[0] > 0       # (1, BS)
    bs = c.shape[-1]
    iot = lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    slot = lax.broadcasted_iota(jnp.int32, (1, k), 1)

    def d2_to(i):                                     # i (1, 1)
        p = jnp.sum(jnp.where(iot == i, c, 0.0), axis=1, keepdims=True)
        sq = (c - p) ** 2                             # (3, BS)
        # (dx^2 + dy^2) + dz^2, the order kernels/ref.py sums in.
        return (sq[0:1] + sq[1:2]) + sq[2:3]

    def argmax(x):
        return first_lane(x == jnp.max(x, axis=1, keepdims=True), iot, bs)

    # First valid lane (valid-prefix layout => lane 0 of real blocks).
    start = argmax(v.astype(jnp.float32))
    mind = jnp.where(v, d2_to(start), NEG)
    mind = jnp.where(iot == start, NEG, mind)

    def body(j, carry):
        m, prev, out = carry
        # Exhaustion contract (kernels/ref.py): unselected valid lanes
        # hold d2 >= 0 > NEG, so an all-pinned vector means k exceeds the
        # valid count — repeat the last valid selection.
        nxt = jnp.where(jnp.max(m, axis=1, keepdims=True) > NEG,
                        argmax(m), prev)
        m = jnp.minimum(m, jnp.where(v, d2_to(nxt), NEG))
        m = jnp.where(iot == nxt, NEG, m)
        return m, nxt, jnp.where(slot == j, nxt, out)

    out = jnp.where(slot == 0, start, 0)
    _, _, out = lax.fori_loop(1, k, body, (mind, start, out))
    idx_ref[0] = out


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def fps_blocks(coords: jax.Array, vmask: jax.Array, *, k: int,
               interpret: bool) -> jax.Array:
    """coords (NB, 3, BS) f32, vmask (NB, 1, BS) {0,1} -> idx (NB, k) i32."""
    nb, _, bs = coords.shape
    kernel = functools.partial(_fps_kernel, k=k)
    idx = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, 3, bs), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 1, bs), lambda b: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, k), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, 1, k), jnp.int32),
        interpret=interpret,
    )(coords.astype(jnp.float32), vmask.astype(jnp.float32))
    return idx[:, 0]
