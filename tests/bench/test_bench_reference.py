"""The plain reference against the program, on the CPU at small sizes:
the index plan (partition, samples, neighbours, interpolation) equal bit
for bit, the logits equal to float32 rounding at HIGHEST precision; and the
control (the reference from float8 operands) failing the serve limit."""
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import clouds, compare, model, reference  # noqa: E402

SPEC = {"points": 1024, "pool": 2, "footprint_m": 1.0, "height_m": 3.0,
        "objects": 6, "object_size_m": [0.15, 0.6], "noise_m": 0.01,
        "floor_share": 0.2}


def small(name):
    cfg = model.load(name)
    st = [dict(s, mlp=[8, 8, w]) for s, w in zip(cfg["sa_stages"],
                                                 (16, 16, 32, 32))]
    return dict(cfg, num_points=1024, impl="xla", sa_stages=st,
                fp_mlp=[[32, 32], [32, 16], [16, 16], [16, 16, 16]],
                head_mlp=[16])


@pytest.mark.parametrize("name", ["pointnet2_ssg_seg_s3dis",
                                  "pointnet2_ssg_seg_scannet"])
def test_plan_equals_the_program(name):
    from repro import core
    cfg = small(name)
    pts, _ = clouds.pool(2**31 + 3, SPEC)
    c, v = pts[0], np.ones(1024, bool)
    th = cfg["th"]
    sizes = reference.stage_sizes(1024, cfg["sa_stages"])
    wc = max(16, int(2 * th * cfg["sa_stages"][0]["rate"]))
    for i, s in enumerate(cfg["sa_stages"]):
        @jax.jit
        def program(c, v, s=s, k_out=sizes[i + 1]):
            part = core.partition(c, v, th=th, on_overflow="silent")
            samp = core.blockwise_fps(part, rate=s["rate"], k_out=k_out,
                                      bs=th, impl="xla")
            nb = core.blockwise_ball_query(part, samp, radius=s["radius"],
                                           num=s["nsample"], w=2 * th,
                                           impl="xla")
            _, idx3, w3 = core.blockwise_interpolate(
                part, samp, samp.coords, wc=wc, bs=th, impl="xla")
            return part, samp, nb, idx3, w3
        part, samp, nb, idx3, w3 = program(jnp.asarray(c), jnp.asarray(v))
        rp = reference.partition(c, v, th)
        rs = reference.sample(rp, s["rate"], sizes[i + 1], th)
        bi, bm = reference.ball_query(rp, rs, s["radius"], s["nsample"],
                                      2 * th)
        ri, rw = reference.interpolation(rp, rs, wc, th)
        np.testing.assert_array_equal(np.asarray(part.perm), rp["perm"])
        np.testing.assert_array_equal(np.asarray(samp.idx), rs["idx"])
        np.testing.assert_array_equal(np.asarray(samp.valid), rs["valid"])
        np.testing.assert_array_equal(np.asarray(nb.idx), bi)
        mask = np.asarray(nb.mask).copy()
        mask[:, 0] = np.asarray(samp.valid)
        np.testing.assert_array_equal(mask, bm)
        back = np.argsort(np.asarray(part.perm))
        np.testing.assert_array_equal(np.asarray(idx3)[back], ri)
        np.testing.assert_allclose(np.asarray(w3)[back], rw, atol=1e-6)
        c, v = rs["centers"], rs["valid"]


@pytest.fixture(scope="module")
def logits():
    from repro.models import pnn
    cfg = small("pointnet2_ssg_seg_s3dis")
    pts, _ = clouds.pool(2**31 + 4, SPEC)
    # Half of the second cloud is padding, as admission pads a short cloud.
    valid = np.ones((2, 1024), bool)
    valid[1, 512:] = False
    pts[1, 512:] = 0.0
    params = model.weights(cfg, jax.random.PRNGKey(3))
    plans = [reference.plan_cloud(p, v, cfg) for p, v in zip(pts, valid)]
    stacked = reference.stack_plans(plans)
    mcfg = model.pnn_config(cfg)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, c, v: pnn.apply_batch(p, mcfg, c, v))(
            params, jnp.asarray(pts), jnp.asarray(valid))
    fwd = jax.jit(reference.forward, static_argnums=3)
    want = fwd(params, jnp.asarray(pts), stacked, None)
    ctrl = fwd(params, jnp.asarray(pts), stacked, "fp8")
    return (np.asarray(got), np.asarray(want), np.asarray(ctrl), valid)


def test_logits_equal_the_program(logits):
    got, want, _, valid = logits
    for g, w, v in zip(got, want, valid):
        assert compare.logit_gap(g[v], w[v]) < 1e-5


def test_float8_control_fails_the_serve_limit(logits):
    _, want, ctrl, valid = logits
    limit = compare.limits("pointnet2_ssg_seg_s3dis", "serve")["logit_gap"]
    gaps = [compare.logit_gap(c[v], w[v]) for c, w, v in
            zip(ctrl, want, valid)]
    assert min(gaps) > limit
