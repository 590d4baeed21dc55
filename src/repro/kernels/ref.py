"""Pure-jnp oracles mirroring each Pallas kernel's exact contract.

Tests sweep shapes/dtypes and assert_allclose kernel-vs-oracle; tie-breaking
(argmax/argmin pick the first extremum) matches by construction because both
sides evaluate the same formulas in the same order.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import INF, NEG


def _fps_one(coords, vmask, k):
    """coords (3, BS), vmask (BS,) -> (k,) i32.

    Exhaustion contract: once every valid point has been selected (k larger
    than the block's valid count), the remaining slots *repeat the last
    valid selection* instead of emitting whatever argmax of an all-pinned
    vector lands on.  An empty block degenerates to repeating index 0."""
    c = coords.astype(jnp.float32)
    v = vmask > 0
    bs = c.shape[-1]

    def d2_to(i):
        sq = (c - c[:, i][:, None]) ** 2
        return (sq[0] + sq[1]) + sq[2]          # the kernel's order

    start = jnp.argmax(v.astype(jnp.float32)).astype(jnp.int32)
    iot = jnp.arange(bs, dtype=jnp.int32)
    mind = jnp.where(v, d2_to(start), NEG)
    mind = jnp.where(iot == start, NEG, mind)

    def step(carry, _):
        m, prev = carry
        # Unselected valid lanes hold d2 >= 0 > NEG; all-pinned means done.
        nxt = jnp.where(jnp.max(m) > NEG,
                        jnp.argmax(m).astype(jnp.int32), prev)
        m = jnp.minimum(m, jnp.where(v, d2_to(nxt), NEG))
        m = jnp.where(iot == nxt, NEG, m)
        return (m, nxt), nxt

    _, rest = jax.lax.scan(step, (mind, start), None, length=k - 1)
    return jnp.concatenate([start[None], rest])


def fps_blocks(coords, vmask, *, k):
    return jax.vmap(lambda c, m: _fps_one(c, m[0], k))(
        coords, vmask)


def _topk_min(d, num):
    iot = jnp.arange(d.shape[-1], dtype=jnp.int32)[None, :]
    idxs, vals = [], []
    for _ in range(num):
        v = jnp.min(d, axis=-1)
        i = jnp.argmin(d, axis=-1).astype(jnp.int32)
        idxs.append(i)
        vals.append(v)
        d = jnp.where(iot == i[:, None], INF, d)
    return jnp.stack(idxs, -1), jnp.stack(vals, -1)


def _sqdist(a, b):
    """a (3, r), b (3, n) -> (r, n) squared distances, summed coordinate
    by coordinate as (dx^2 + dy^2) + dz^2 — the kernels' order, so the two
    backends agree bit for bit."""
    diff = a[:, :, None] - b[:, None, :]
    sq = diff * diff
    return (sq[0] + sq[1]) + sq[2]


def ball_query_blocks(centers, cmask, window, wmask, *, radius, num):
    r2 = jnp.float32(radius) ** 2

    def one(c, cm, w, wm):
        d = _sqdist(c.astype(jnp.float32), w.astype(jnp.float32))
        d = jnp.where(wm[0] > 0, d, INF)
        cnt = jnp.where(cm[0] > 0,
                        jnp.sum(((d <= r2) & (wm[0][None, :] > 0)),
                                axis=-1), 0).astype(jnp.int32)
        idx, val = _topk_min(d, num)
        return idx, val, cnt

    return jax.vmap(one)(centers, cmask, window, wmask)


def knn_blocks(queries, window, wmask, *, k):
    def one(q, w, wm):
        d = _sqdist(q.astype(jnp.float32), w.astype(jnp.float32))
        d = jnp.where(wm[0] > 0, d, INF)
        return _topk_min(d, k)

    return jax.vmap(one)(queries, window, wmask)


def gather_blocks(window_feats, idx):
    """Out-of-range idx (negative or >= W) fetches zeros — the one-hot
    kernel's contract, which the backward relies on to drop their rows."""
    w = window_feats.shape[-2]

    def one(f, i):
        ok = (i >= 0) & (i < w)
        return jnp.where(ok[:, None], f[jnp.clip(i, 0, w - 1)], 0)

    return jax.vmap(one)(window_feats, idx)


def scatter_add_blocks(g, idx, *, w):
    """gather_blocks' backward oracle: g (NB, M, C), idx (NB, M) ->
    (NB, W, C); out-of-range idx rows are dropped (their forward rows
    fetched zeros)."""
    c = g.shape[-1]

    def one(gg, i):
        ok = (i >= 0) & (i < w)
        safe = jnp.clip(i, 0, w - 1)
        return jnp.zeros((w, c), g.dtype).at[safe].add(
            jnp.where(ok[:, None], gg, 0))

    return jax.vmap(one)(g, idx)


def fractal_level_blocks(coords, vmask, mid, *, da, db):
    def one(c, vm, m):
        v = vm[0] > 0
        xa, xb = c[da], c[db]
        side = (xa > m[0]) & v
        left = v & ~side
        stats = jnp.stack([
            jnp.min(jnp.where(left, xb, INF)),
            jnp.max(jnp.where(left, xb, NEG)),
            jnp.min(jnp.where(side, xb, INF)),
            jnp.max(jnp.where(side, xb, NEG)),
        ])
        return side.astype(jnp.int32), jnp.sum(left.astype(jnp.int32)), stats

    return jax.vmap(one)(coords, vmask, mid)
