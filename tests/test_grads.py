"""Gradient contract of the execute-phase dispatch ops (kernels/vjp.py,
docs/DESIGN.md §4): gather's VJP matches the ref oracle's, index producers
carry zero cotangents, ``jax.grad`` through ``pnn.apply`` agrees between
``impl="pallas"`` (interpret) and ``impl="xla"`` at 1e-4, and a multi-step
fine-tune on an 8-device host mesh lowers the loss."""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import synthetic
from repro.kernels import ops, ref
from repro.models import pnn

jax.config.update("jax_platform_name", "cpu")

IMPLS = ["xla", "pallas"]


def blocks(seed, nb, bs, max_valid=None):
    rng = np.random.default_rng(seed)
    coords = rng.normal(0, 1, (nb, bs, 3)).astype(np.float32)
    nvalid = rng.integers(1, (max_valid or bs) + 1, nb)
    mask = np.arange(bs)[None, :] < nvalid[:, None]
    return jnp.asarray(coords), jnp.asarray(mask)


# ---------------------------------------------------------------------------
# Per-op VJPs against jax.vjp of the ref oracle.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("chunk", [None, 2])
def test_gather_vjp_matches_ref_oracle(impl, chunk):
    """d(window_feats) through the dispatch layer == jax.vjp of the jnp
    oracle — including out-of-range idx rows, which fetched zeros forward
    and must receive nothing backward."""
    rng = np.random.default_rng(0)
    feats = jnp.asarray(rng.normal(0, 1, (3, 40, 9)).astype(np.float32))
    idx = jnp.asarray(rng.integers(-5, 50, (3, 17)), jnp.int32)  # oob both
    g = jnp.asarray(rng.normal(0, 1, (3, 17, 9)).astype(np.float32))

    ro, rvjp = jax.vjp(lambda f: ref.gather_blocks(f, idx), feats)
    (rg,) = rvjp(g)
    o, vjp = jax.vjp(
        lambda f: ops.gather_blocks(f, idx, impl=impl, chunk=chunk), feats)
    (df,) = vjp(g)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ro),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(df), np.asarray(rg),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", IMPLS)
def test_index_producers_zero_cotangents(impl):
    """FPS / ball query / kNN are index producers: every output —
    including the float d2 — carries a zero cotangent to every input."""
    coords, mask = blocks(1, 2, 40)

    d2 = lambda c: ops.knn_blocks(c, c, mask, k=3, impl=impl)[1]
    g = jax.grad(lambda c: jnp.sum(d2(c)))(coords)
    assert float(jnp.abs(g).sum()) == 0.0

    bq = lambda c: ops.ball_query_blocks(c, mask, c, mask, radius=0.7,
                                         num=4, impl=impl)[1]
    g = jax.grad(lambda c: jnp.sum(bq(c)))(coords)
    assert float(jnp.abs(g).sum()) == 0.0

    # fps output is integer (tangent type float0): grad through a loss
    # that *uses* the indices must flow only through the explicit gather,
    # not through the selection itself.  The selection is discrete, so
    # the grad is exactly the oracle of "gather at the selected slots".
    def loss(c):
        idx = ops.fps_blocks(c, mask, k=4, impl=impl)
        picked = jnp.take_along_axis(c, idx[..., None], axis=1)
        return jnp.sum(picked)

    g = jax.grad(loss)(coords)
    assert np.isfinite(np.asarray(g)).all()
    idx = ops.fps_blocks(coords, mask, k=4, impl=impl)
    oracle = jax.grad(lambda c: jnp.sum(jnp.take_along_axis(
        c, idx[..., None], axis=1)))(coords)
    np.testing.assert_allclose(np.asarray(g), np.asarray(oracle),
                               rtol=1e-6)


@pytest.mark.parametrize("impl", IMPLS)
def test_fractal_level_zero_cotangents(impl):
    coords, mask = blocks(2, 3, 33)
    mid = jnp.zeros((3,), jnp.float32)

    def f(c, m):
        _, _, stats = ops.fractal_level_blocks(c, m, mid, da=0, db=1,
                                               impl=impl)
        return jnp.sum(jnp.where(jnp.abs(stats) < 1e30, stats, 0.0))

    g = jax.grad(f)(coords, mask.astype(jnp.float32))
    assert float(jnp.abs(g).sum()) == 0.0


# ---------------------------------------------------------------------------
# End-to-end: grad through pnn.apply, pallas (interpret) vs xla.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("task,n,th", [("cls", 192, 32), ("seg", 256, 64)])
def test_pnn_grad_parity(task, n, th):
    """jax.value_and_grad of a PNN loss compiles and runs with
    impl="pallas" (no xla fallback) and the grads agree with the oracle
    backend at 1e-4 — cls and seg presets."""
    cfg = pnn.PNNConfig(variant="pointnet2", task=task, n_points=n,
                        point_ops="bppo", th=th)
    params = pnn.init(jax.random.PRNGKey(0), cfg)
    batch = (synthetic.classification_batch if task == "cls"
             else synthetic.segmentation_batch)
    pts, labels = batch(0, 0, 1, n)

    def loss(p, impl):
        mcfg = dataclasses.replace(cfg, impl=impl)
        logits = pnn.apply(p, mcfg, pts[0])
        ll = jax.nn.log_softmax(logits)
        if task == "cls":
            return -ll[labels[0]]
        return -jnp.mean(jnp.take_along_axis(ll, labels[0][:, None],
                                             axis=-1))

    vp, gp = jax.jit(jax.value_and_grad(
        lambda p: loss(p, "pallas")))(params)
    vx, gx = jax.jit(jax.value_and_grad(lambda p: loss(p, "xla")))(params)
    np.testing.assert_allclose(float(vp), float(vx), rtol=1e-4, atol=1e-4)
    for (kp, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(gp),
                               jax.tree_util.tree_leaves_with_path(gx)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=jax.tree_util.keystr(kp))
    norms = [float(jnp.abs(x).sum()) for x in jax.tree.leaves(gp)]
    assert sum(v > 0 for v in norms) > len(norms) * 0.7, norms


def test_pnn_train_step_runs_pallas():
    """One full AdamW fine-tune step with impl="pallas" end to end (the
    escape hatch is gone: no wrap-with-xla needed under jax.grad)."""
    from repro.train import pnn as train_pnn

    cfg = train_pnn.TrainConfig(preset="pointnet2_cls", n_points=128,
                                th=32, batch=2, steps=1, impl="pallas")
    mcfg = train_pnn.model_config(cfg)
    assert mcfg.impl == "pallas"
    params, _, info = train_pnn.fit(cfg, log=lambda *_: None)
    assert len(info["history"]) == 1
    assert np.isfinite(info["history"][0]["loss"])


# ---------------------------------------------------------------------------
# Multi-step fine-tune on the 8-device host mesh (subprocess: device count
# must be set before jax initializes).
# ---------------------------------------------------------------------------

TRAIN_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    from repro.train import pnn as train_pnn

    cfg = train_pnn.TrainConfig(preset="pointnet2_cls", n_points=128,
                                th=32, batch=8, steps=6, lr=3e-3,
                                impl="xla", mesh="auto")
    params, _, info = train_pnn.fit(cfg, log=lambda *_: None)
    h = info["history"]
    print(json.dumps({
        "n_dev": len(jax.devices()),
        "losses": [s["loss"] for s in h],
    }))
""")


def test_multidevice_finetune_lowers_loss():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", TRAIN_PROG],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    assert data["n_dev"] == 8
    losses = data["losses"]
    assert len(losses) == 6 and all(np.isfinite(losses))
    assert losses[-1] < losses[0], losses


MESH_PALLAS_GRAD_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from repro.data import synthetic
    from repro.dist import elastic
    from repro.models import pnn
    from repro.train import pnn as train_pnn

    cfg = train_pnn.TrainConfig(preset="pointnet2_seg", n_points=128,
                                th=32, batch=4, impl="pallas")
    mcfg = train_pnn.model_config(cfg)
    params = pnn.init(jax.random.PRNGKey(0), mcfg)
    pts, labels = synthetic.segmentation_batch(1, 0, 4, 128)
    batch = {"points": pts, "labels": labels}
    grad = jax.value_and_grad(train_pnn.loss_fn, has_aux=True)
    (loss_s, _), g_s = jax.jit(lambda p, b: grad(p, mcfg, b))(params, batch)
    mesh = elastic.make_mesh(model_axis=2)
    split = pnn.batch_spec(mcfg, mesh, 4)
    batch_m = jax.device_put(batch, NamedSharding(mesh, split))
    (loss_m, _), g_m = jax.jit(lambda p, b: grad(p, mcfg, b, mesh))(
        params, batch_m)
    gerr = max(float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-12))
               for a, b in zip(jax.tree.leaves(g_m), jax.tree.leaves(g_s)))
    print(json.dumps({"n_dev": len(jax.devices()), "split": str(split),
                      "loss_err": abs(float(loss_m) - float(loss_s)),
                      "grad_err": gerr}))
""")


def test_mesh_pallas_grads_match_single_device():
    """The Pallas point ops on a 4-device mesh run inside the shard_map of
    models/pnn.apply_batch, one cloud per device, with the params
    replicated into it: the loss and every parameter gradient equal the
    single-device ones (the replicated params' cotangents sum over the
    devices' clouds)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", MESH_PALLAS_GRAD_PROG],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    assert data["n_dev"] == 4
    assert data["split"] == "PartitionSpec(('data', 'model'),)"
    assert data["loss_err"] <= 1e-5
    assert data["grad_err"] <= 1e-4
