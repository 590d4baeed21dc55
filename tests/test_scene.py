"""repro.scene: tile -> halo -> stitch (docs/DESIGN.md §10).

Covers the tiler contract (tiles = disjoint coarse-leaf covers, halo ring
within radius of the tile bbox), the owner-tile stitching rule (halo rows
are never observed), the chunked scene generator (counter-based RNG:
chunk-size invariant), and the §10 exactness oracle: with halo=0 and the
single-SA-stage model, stitched tile-wise seg logits equal a direct
whole-scene forward (same th/strategy/impl) — tiles are exact subtrees of
the global fractal tree, re-derived per tile via the dim0 split-phase.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import core, scene
from repro.data import synthetic
from repro.models import pnn

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# Scene generator (chunked, counter-based RNG).
# ---------------------------------------------------------------------------

def test_scene_generator_chunk_invariant():
    """Per-point fold_in keys: the stream must not depend on chunking."""
    p1, l1 = synthetic.scene(0, 3000, chunk=256)
    p2, l2 = synthetic.scene(0, 3000, chunk=3000)
    np.testing.assert_array_equal(p1, p2)
    np.testing.assert_array_equal(l1, l2)
    assert p1.shape == (3000, 3) and p1.dtype == np.float32
    assert l1.shape == (3000,) and l1.dtype == np.int32
    assert set(np.unique(l1)) <= set(range(synthetic.NUM_SHAPES))
    p3, _ = synthetic.scene(1, 3000)
    assert not np.array_equal(p1, p3)
    with pytest.raises(ValueError):
        synthetic.scene(0, 0)


# ---------------------------------------------------------------------------
# Tiler: coverage, halo ring.
# ---------------------------------------------------------------------------

def test_tile_scene_covers_disjointly():
    pts, _ = synthetic.scene(0, 4096, objects=8)
    plan = scene.tile_scene(pts, tile_points=512)
    assert plan.num_tiles >= 2
    owned = np.concatenate([t.owned for t in plan.tiles])
    assert sorted(owned.tolist()) == list(range(4096))   # exact cover
    for t in plan.tiles:
        assert 0 < t.n_owned <= 512
        assert t.dim0 == t.depth % 3
        tpts = pts[t.owned]
        np.testing.assert_allclose(tpts.min(0), t.lo)
        np.testing.assert_allclose(tpts.max(0), t.hi)
    assert (scene.owner_of(plan) >= 0).all()


def test_halo_ring_contract():
    pts, _ = synthetic.scene(0, 4096, objects=8)
    halo_r = 0.4
    plan = scene.tile_scene(pts, tile_points=512, halo=halo_r,
                            max_halo_points=64)
    assert plan.halo_points > 0
    for t in plan.tiles:
        assert len(t.halo) <= 64
        assert not set(t.halo.tolist()) & set(t.owned.tolist())
        if len(t.halo):
            d = np.maximum(np.maximum(t.lo - pts[t.halo],
                                      pts[t.halo] - t.hi), 0.0)
            assert (np.sqrt((d * d).sum(-1)) <= halo_r + 1e-6).all()
        # tile cloud layout: owned prefix, halo tail
        assert t.indices.shape == (t.n,)
        np.testing.assert_array_equal(t.indices[:t.n_owned], t.owned)
    # halo off -> no context points anywhere
    plan0 = scene.tile_scene(pts, tile_points=512, halo=0.0)
    assert plan0.halo_points == 0


def test_stitch_owner_tile_priority():
    """Halo rows carry sentinels; stitched output must never contain one —
    the owner-tile rule resolves every halo-overlap point."""
    pts, _ = synthetic.scene(0, 2048, objects=4)
    plan = scene.tile_scene(pts, tile_points=256, halo=0.5,
                            max_halo_points=64)
    assert plan.halo_points > 0
    outputs = {}
    for t in plan.tiles:
        rows = np.full((t.n, 3), float(t.tid), np.float32)
        rows[t.n_owned:] = np.nan                       # halo sentinel
        outputs[t.tid] = rows
    out = scene.stitch(plan, outputs, 3)
    assert np.isfinite(out).all()                       # no halo row leaked
    np.testing.assert_array_equal(out[:, 0],
                                  scene.owner_of(plan).astype(np.float32))
    # row-count mismatches are loud, not silent
    outputs[plan.tiles[0].tid] = outputs[plan.tiles[0].tid][:-1]
    with pytest.raises(ValueError, match="rows"):
        scene.stitch(plan, outputs, 3)


def test_scene_engine_rejects_tiny_tiles():
    with pytest.raises(ValueError, match="tile_points"):
        scene.SceneEngine(scene.SceneConfig(tile_points=64, th=256))


def test_scene_engine_fails_fast_on_overflowed_tiling():
    """An unsplittable (all-duplicate) region deeper than the depth cap
    must raise the actionable overflow error before any tile is
    submitted — not an opaque bucket-ladder error mid-stream."""
    pts = np.zeros((2048, 3), np.float32)
    eng = scene.SceneEngine(scene.SceneConfig(tile_points=512, th=64,
                                              impl="xla", halo=0.0))
    with pytest.raises(core.FractalOverflowError, match="tile_points=512"):
        eng.infer(pts)


def test_scene_surfaces_tile_internal_overflow():
    """An unsplittable cluster bigger than th but smaller than
    tile_points passes the coarse-plan check, so it must surface from
    the serve plan executable instead (ServeConfig.on_overflow) — never
    silent truncation."""
    import warnings
    pts, _ = synthetic.scene(0, 2048, objects=4)
    pts[300:500] = pts[300]                     # 200 duplicates, th=64
    cfg = scene.SceneConfig(
        tile_points=512, halo=0.0, th=64, impl="xla", microbatch=2,
        stages=(pnn.SAStage(0.25, 0.25, 8, (8, 8)),), fp_widths=((8,),))
    eng = scene.SceneEngine(cfg)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        logits, plan = eng.infer(pts)
        jax.effects_barrier()
    assert not plan.overflowed                  # coarse tiling is fine
    assert logits.shape == (2048, cfg.num_classes)
    assert [w for w in caught
            if issubclass(w.category, core.FractalOverflowWarning)]


# ---------------------------------------------------------------------------
# §10 exactness oracle: stitched tiles == whole-scene forward.
# ---------------------------------------------------------------------------

def _lattice_scene(seed, n, spacing=0.1):
    """A jittered lattice of ``n`` (a power of two) points, shuffled.

    Sites sit on a 2^a x 2^b x 2^c grid (a >= b >= c, round-robin over the
    bits of n); each point moves by under a fifth of the spacing.  A
    fractal split then falls between the two middle planes of the node
    (the mid of the extremes is within 0.2 spacing of the plane gap, the
    points within 0.2 of their planes), so every node halves exactly.
    Coarse tiles and model leaves are therefore whole subtrees at fixed
    depths log2(n / tile_points) and log2(n / th), every leaf's FPS quota
    rate * th is whole (no rounding, so no budget truncation anywhere), and
    every leaf sits log2(tile_points / th) levels below its tile — the two
    exactness preconditions hold by construction, for any seed."""
    bits = n.bit_length() - 1
    assert 1 << bits == n
    dims = [bits // 3 + (i < bits % 3) for i in range(3)]
    axes = [np.arange(1 << d) for d in dims]
    grid = np.stack(np.meshgrid(*axes, indexing="ij"), -1).reshape(-1, 3)
    rng = np.random.default_rng(seed)
    pts = grid + rng.uniform(-0.2, 0.2, grid.shape)
    return (rng.permutation(pts) * spacing).astype(np.float32)


def _check_exactness_preconditions(pts, *, n, tile_points, th, rate):
    """The two static-budget conditions under which tiling is exact (§10):
    the whole-scene run must not truncate sample quotas (k_out), and every
    model leaf must sit >= 2 levels below its tile node (so parent search
    windows stay inside the tile).  ``_lattice_scene`` meets both by
    construction, the clustered scene of the pallas case at its seed;
    assert them so a change to the partition or the data fails loudly."""
    part_g = jax.jit(lambda p: core.partition(p, th=th))(pts)
    k_out = int(round(rate * n))
    samp = core.blockwise_fps(part_g, rate=rate, k_out=k_out, bs=th,
                              impl="xla")
    assert int(samp.total) <= k_out, (int(samp.total), k_out)
    part_c = jax.jit(lambda p: core.partition(p, th=tile_points))(pts)
    isl_c = np.asarray(part_c.is_leaf)
    sc = np.asarray(part_c.leaf_start)
    rc = np.asarray(part_c.leaf_rsize)
    vc = np.asarray(part_c.leaf_vsize)
    dc = np.asarray(part_c.leaf_depth)
    isl_g = np.asarray(part_g.is_leaf)
    sg = np.asarray(part_g.leaf_start)[isl_g]
    dg = np.asarray(part_g.leaf_depth)[isl_g]
    for i in np.nonzero(isl_c)[0]:
        if vc[i] == 0:
            continue
        inside = (sg >= sc[i]) & (sg < sc[i] + rc[i])
        assert dg[inside].min() >= dc[i] + 2, f"tile at depth {dc[i]}"


@pytest.mark.parametrize("impl,seed,n,tile_points,data", [
    # Every split of the lattice halves exactly: tiles at one depth.
    pytest.param("xla", 3, 4096, 1024, "lattice", id="xla-3-4096-1024"),
    # Clustered objects: an uneven tree whose tiles sit at several depths,
    # so each tile's own dim0 matters (asserted below).  Interpret mode on
    # the CPU.
    pytest.param("pallas", 8, 2048, 512, "scene", id="pallas-8-2048-512"),
])
def test_scene_matches_whole_forward(impl, seed, n, tile_points, data):
    """Acceptance oracle: halo=0 + single-SA-stage model + per-tile dim0
    -> stitched tile-wise logits match the direct whole-scene forward
    (same th/strategy/impl) to 1e-4 on owned points (all points: with
    halo=0 every tile row is owned)."""
    th = 64
    cfg = pnn.scene_seg(n=n, th=th, impl=impl, widths=(16, 16), fp=(16, 16))
    if data == "lattice":
        pts_np = _lattice_scene(seed, n)
    else:
        pts_np, _ = synthetic.scene(seed, n, objects=n // 512)
    pts = jnp.asarray(pts_np)
    _check_exactness_preconditions(pts, n=n, tile_points=tile_points, th=th,
                                   rate=cfg.stages[0].rate)

    params = pnn.init(jax.random.PRNGKey(0), cfg)
    direct = np.asarray(jax.jit(lambda c: pnn.apply(params, cfg, c))(pts))

    scfg = scene.SceneConfig(tile_points=tile_points, halo=0.0, th=th,
                             impl=impl, microbatch=2, stages=cfg.stages,
                             fp_widths=cfg.fp_widths)
    eng = scene.SceneEngine(scfg, params=params)
    out, plan = eng.infer(pts_np)
    assert plan.num_tiles >= 4
    if data == "scene":
        assert len({t.dim0 for t in plan.tiles}) >= 2, "tiles share one dim0"
    np.testing.assert_allclose(out, direct, atol=1e-4, rtol=1e-4)
    # every tile hit one of the two bucket executables, each traced once
    traces = eng.engine.plans.traces
    assert all(v == 1 for v in traces.values()), dict(traces)


def test_scene_engine_multistage_halo_smoke():
    """The general path (2-stage model, halo on): approximate at borders
    by design, but structurally sound — finite logits, full coverage,
    bounded tile clouds, streamed results drained."""
    n = 2048
    pts, _ = synthetic.scene(0, n, objects=4)
    cfg = scene.SceneConfig(
        tile_points=512, halo=0.3, max_halo_points=128, th=64,
        impl="xla", microbatch=2,
        stages=(pnn.SAStage(0.25, 0.25, 8, (8, 8)),
                pnn.SAStage(0.25, 0.5, 8, (8, 16))),
        fp_widths=((16,), (8,)))
    eng = scene.SceneEngine(cfg)
    logits, plan = eng.infer(pts)
    assert logits.shape == (n, cfg.num_classes)
    assert np.isfinite(logits).all()
    assert plan.halo_points > 0
    assert plan.max_tile_n <= cfg.max_tile_cloud()
    assert not eng.engine.results            # all results drained
    st = eng.stats()
    assert st["served"] == plan.num_tiles
