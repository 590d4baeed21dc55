"""Pallas kernels (interpret mode) vs pure-jnp oracles, swept over shapes."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import ref as kref

jax.config.update("jax_platform_name", "cpu")


def blocks(seed, nb, bs, dtype=jnp.float32, frac_valid=0.8):
    rng = np.random.default_rng(seed)
    coords = rng.normal(0, 1, (nb, bs, 3)).astype(np.float32)
    nvalid = rng.integers(max(1, int(frac_valid * bs) - 4), bs + 1, nb)
    mask = np.arange(bs)[None, :] < nvalid[:, None]
    return jnp.asarray(coords, dtype), jnp.asarray(mask)


@pytest.mark.parametrize("nb,bs,k", [(4, 64, 16), (2, 128, 8), (7, 200, 5),
                                     (1, 256, 64), (3, 96, 1)])
def test_fps_kernel_matches_ref(nb, bs, k):
    coords, mask = blocks(0, nb, bs)
    a = ops.fps_blocks(coords, mask, k=k, impl="pallas")
    b = ops.fps_blocks(coords, mask, k=k, impl="xla")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fps_kernel_dtypes(dtype):
    coords, mask = blocks(1, 3, 128, dtype)
    a = ops.fps_blocks(coords, mask, k=8, impl="pallas")
    b = ops.fps_blocks(coords, mask, k=8, impl="xla")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fps_kernel_samples_valid_first():
    coords, mask = blocks(2, 4, 64, frac_valid=0.4)
    idx = np.asarray(ops.fps_blocks(coords, mask, k=8, impl="pallas"))
    m = np.asarray(mask)
    for b in range(4):
        nv = m[b].sum()
        take = min(8, nv)
        assert m[b][idx[b][:take]].all(), "sampled an invalid point"
        assert len(np.unique(idx[b][:take])) == take, "duplicate sample"


# kc 5 / 9 pad to 8 / 16 rows, so 16 / 8 leaves share a grid step; 13
# leaves is no multiple of either, and the middle leaf's centers are masked.
@pytest.mark.parametrize("nb,kc,w,num", [(3, 16, 128, 8), (2, 32, 256, 16),
                                         (5, 8, 64, 4), (1, 64, 512, 32),
                                         (13, 5, 64, 32), (13, 9, 64, 32),
                                         (13, 9, 200, 16), (17, 65, 96, 8)])
def test_ball_query_kernel_matches_ref(nb, kc, w, num):
    rng = np.random.default_rng(3)
    win, wmask = blocks(4, nb, w)
    ci = rng.integers(0, w, (nb, kc))
    centers = jnp.take_along_axis(win, jnp.asarray(ci)[..., None], axis=1)
    cmask = jnp.ones((nb, kc), bool)
    if nb > 1:
        cmask = cmask.at[nb // 2].set(False)
    a_idx, a_d2, a_cnt = ops.ball_query_blocks(
        centers, cmask, win, wmask, radius=0.7, num=num, impl="pallas")
    b_idx, b_d2, b_cnt = ops.ball_query_blocks(
        centers, cmask, win, wmask, radius=0.7, num=num, impl="xla")
    np.testing.assert_array_equal(np.asarray(a_idx), np.asarray(b_idx))
    np.testing.assert_array_equal(np.asarray(a_d2), np.asarray(b_d2))
    np.testing.assert_array_equal(np.asarray(a_cnt), np.asarray(b_cnt))
    if nb > 1:
        assert not np.asarray(a_cnt)[nb // 2].any()


def test_ball_query_semantics():
    # nearest-first, in-radius, count correct vs brute force numpy
    rng = np.random.default_rng(5)
    win, wmask = blocks(6, 2, 96)
    centers = win[:, :5, :]
    cmask = jnp.ones((2, 5), bool)
    idx, d2, cnt = ops.ball_query_blocks(centers, cmask, win, wmask,
                                         radius=0.9, num=8, impl="pallas")
    wn, mn = np.asarray(win), np.asarray(wmask)
    for b in range(2):
        for i in range(5):
            d = ((wn[b] - wn[b, i]) ** 2).sum(-1)
            d[~mn[b]] = np.inf
            true_cnt = int((d <= 0.81).sum())
            assert int(cnt[b, i]) == true_cnt
            order = np.argsort(d, kind="stable")[:8]
            got = np.asarray(idx[b, i])
            valid_k = min(8, true_cnt)
            np.testing.assert_array_equal(got[:valid_k], order[:valid_k])


# q 32 packs 4 leaves per grid step (13 leaves: no multiple of 4), q 5
# packs 16; the middle leaf's window is empty.
@pytest.mark.parametrize("nb,q,w,k", [(3, 32, 128, 3), (2, 64, 96, 5),
                                      (1, 16, 256, 8), (13, 32, 24, 3),
                                      (13, 5, 40, 3)])
def test_knn_kernel_matches_ref(nb, q, w, k):
    win, wmask = blocks(7, nb, w)
    queries, _ = blocks(8, nb, q)
    if nb > 1:
        wmask = wmask.at[nb // 2].set(False)
    a_idx, a_d2 = ops.knn_blocks(queries, win, wmask, k=k, impl="pallas")
    b_idx, b_d2 = ops.knn_blocks(queries, win, wmask, k=k, impl="xla")
    np.testing.assert_array_equal(np.asarray(a_idx), np.asarray(b_idx))
    np.testing.assert_array_equal(np.asarray(a_d2), np.asarray(b_d2))


@pytest.mark.parametrize("nb,w,c,m", [(3, 64, 16, 20), (2, 128, 32, 64),
                                      (1, 96, 8, 7)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gather_kernel_matches_ref(nb, w, c, m, dtype):
    rng = np.random.default_rng(9)
    feats = jnp.asarray(rng.normal(0, 1, (nb, w, c)), dtype)
    idx = jnp.asarray(rng.integers(0, w, (nb, m)), jnp.int32)
    a = ops.gather_blocks(feats, idx, impl="pallas")
    b = ops.gather_blocks(feats, idx, impl="xla")
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=1e-6)


@pytest.mark.parametrize("da,db", [(0, 1), (1, 2), (2, 0)])
def test_fractal_engine_kernel_matches_ref(da, db):
    coords, mask = blocks(10, 6, 160)
    mid = jnp.asarray(np.random.default_rng(11).normal(0, 0.5, (6,)),
                      jnp.float32)
    a = ops.fractal_level_blocks(coords, mask, mid, da=da, db=db,
                                 impl="pallas")
    b = ops.fractal_level_blocks(coords, mask, mid, da=da, db=db, impl="xla")
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6)


def test_fractal_engine_pipelined_stats_enable_child_mids():
    """Fig. 9 pipeline: the child midpoints derived from the kernel's fused
    child-extrema equal what a fresh min/max traversal would compute."""
    coords, mask = blocks(12, 4, 128)
    x = np.asarray(coords)
    m = np.asarray(mask)
    mids0 = jnp.asarray(
        [(x[b][m[b], 0].max() + x[b][m[b], 0].min()) / 2 for b in range(4)],
        jnp.float32)
    side, lcnt, stats = ops.fractal_level_blocks(coords, mask, mids0,
                                                 da=0, db=1, impl="pallas")
    side = np.asarray(side)
    stats = np.asarray(stats)
    for b in range(4):
        left = m[b] & (side[b] == 0)
        right = m[b] & (side[b] == 1)
        if left.any():
            want = (x[b][left, 1].min() + x[b][left, 1].max()) / 2
            got = (stats[b, 0] + stats[b, 1]) / 2
            np.testing.assert_allclose(got, want, rtol=1e-6)
        if right.any():
            want = (x[b][right, 1].min() + x[b][right, 1].max()) / 2
            got = (stats[b, 2] + stats[b, 3]) / 2
            np.testing.assert_allclose(got, want, rtol=1e-6)
