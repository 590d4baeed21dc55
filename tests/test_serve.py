"""repro.serve: bucket admission, deadline batching, plan-cache warmth.

Covers the DESIGN.md §9 contract: minimal-fitting bucket selection, padded
results equal to the unpadded oracle on real points, plan-cache hit on the
second request of a bucket, exactly one compile per (bucket, impl) across
a mixed-size stream (trace counter), deadline flush of a partially filled
microbatch, and mesh dispatch equal to the single-device path.
"""
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

from repro import serve
from repro.data import synthetic
from repro.kernels import ops as kops
from repro.models import pnn

jax.config.update("jax_platform_name", "cpu")


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# Admission: bucket selection + padding.
# ---------------------------------------------------------------------------

def test_bucket_select_minimal_fitting():
    policy = serve.BucketPolicy((16384, 4096, 65536))   # normalized sorted
    assert policy.buckets == (4096, 16384, 65536)
    assert policy.select(1) == 4096
    assert policy.select(4096) == 4096                  # exact fit
    assert policy.select(4097) == 16384                 # minimal, not max
    assert policy.select(65536) == 65536
    with pytest.raises(ValueError, match="exceeds"):
        policy.select(65537)
    with pytest.raises(ValueError, match="non-empty"):
        policy.select(0)
    with pytest.raises(ValueError, match="positive"):
        serve.BucketPolicy(())


def test_pad_points_contract():
    coords = jnp.arange(15.0).reshape(5, 3)
    padded, valid = kops.pad_points(coords, 8)
    assert padded.shape == (8, 3) and valid.shape == (8,)
    np.testing.assert_array_equal(np.asarray(padded[:5]), np.asarray(coords))
    assert np.asarray(valid).tolist() == [True] * 5 + [False] * 3
    # existing invalid slots survive; no-op when already at size
    c2, v2 = kops.pad_points(coords, 5, valid=jnp.array([1, 1, 0, 1, 1],
                                                        bool))
    assert c2.shape == (5, 3) and not bool(v2[2])
    with pytest.raises(ValueError, match="pad"):
        kops.pad_points(coords, 4)


def test_scene_bucket_admission_minimal_fitting():
    """Scene-scale ladder admission: pad() lands each cloud in its minimal
    bucket with exactly the real points valid (satellite for §10: tile
    clouds of 3–16k points flow through these buckets)."""
    policy = serve.BucketPolicy((4096, 16384, 65536))
    for n, want in [(3000, 4096), (4096, 4096), (4097, 16384),
                    (12000, 16384), (16384, 16384), (16385, 65536)]:
        b, c, v = policy.pad(jnp.zeros((n, 3), jnp.float32))
        assert b == want and c.shape == (want, 3) and v.shape == (want,)
        assert int(v.sum()) == n and bool(v[:n].all())


def untruncated_n_points(part, rate: float, n: int) -> int:
    """The smallest ``n_points`` whose stage-0 sample budget
    ``round(rate * n_points)`` holds every block-wise FPS quota of ``part``.

    Each leaf's quota rounds ``rate * vsize`` by at most 1/2, so the quotas
    of L leaves sum to at most ``rate * n + L / 2`` — which can exceed
    ``round(rate * n)`` (per-leaf rounding adds up), truncating samples.
    A budget at the bound never truncates, whatever the data."""
    leaves = int(jnp.sum(part.is_leaf))
    return int(np.ceil((rate * n + leaves / 2) / rate))


@pytest.mark.parametrize("bucket,n", [(4096, 3000), (16384, 12000)])
def test_padded_matches_unpadded_oracle_scene_buckets(bucket, n):
    """§9 padding invisibility at the scene-scale buckets (previously only
    exercised at 256): the forward over a cloud padded to 4096/16384
    equals the unpadded forward on the real points.

    Window placement keys on valid counts (window_view), so the large
    invalid tail cannot move search windows; the single-SA-stage model
    bounds CPU cost.  §9 holds while no sample budget truncates, so the
    unpadded oracle's budget is sized by the quota bound
    (``untruncated_n_points``) and the bucket's budget is checked against
    the same bound."""
    cfg = pnn.scene_seg(n=n, th=256, impl="xla", widths=(16, 16),
                        fp=(16, 16))
    params = pnn.init(jax.random.PRNGKey(0), cfg)
    pts = jnp.asarray(synthetic.scene(0, n)[0])

    from repro import core
    rate = cfg.stages[0].rate
    part = jax.jit(lambda p: core.partition(p, th=256))(pts)
    n_budget = untruncated_n_points(part, rate, n)
    assert n_budget <= bucket, "bucket budget below the quota bound"
    cfg = dataclasses.replace(cfg, n_points=n_budget)
    samp = core.blockwise_fps(part, rate=rate, k_out=cfg.stage_sizes()[1],
                              bs=256, impl="xla")
    assert int(samp.total) <= cfg.stage_sizes()[1]

    oracle = np.asarray(jax.jit(
        lambda c: pnn.apply(params, cfg, c))(pts))
    padded, valid = kops.pad_points(pts, bucket)
    cfg_b = dataclasses.replace(cfg, n_points=bucket)
    out = np.asarray(jax.jit(
        lambda c, v: pnn.apply(params, cfg_b, c, valid=v))(padded, valid))
    np.testing.assert_allclose(out[:n], oracle, rtol=1e-5, atol=1e-5)


def test_padded_matches_unpadded_oracle():
    """Bucket padding is invisible: the padded forward equals the unpadded
    oracle on the real points (seg covers FPS + grouping + interpolation).

    Sizes are chosen so no sample/window truncation occurs (w = 2*th covers
    every parent; quota sum fits k_out) — see DESIGN.md §9 for why padding
    is only exact under those conditions."""
    n, bucket, th = 200, 256, 64
    cfg = pnn.PNNConfig(variant="pointnet2", task="seg", n_points=n,
                        point_ops="bppo", th=th, impl="xla")
    params = pnn.init(jax.random.PRNGKey(0), cfg)
    pts, _ = synthetic.segmentation_batch(0, 0, 1, n)
    oracle = np.asarray(pnn.apply(params, cfg, pts[0]))

    padded, valid = kops.pad_points(pts[0], bucket)
    cfg_b = dataclasses.replace(cfg, n_points=bucket)
    out = np.asarray(pnn.apply(params, cfg_b, padded, valid=valid))
    np.testing.assert_allclose(out[:n], oracle, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Queue: FIFO packing + deadline semantics (pure, no compiles).
# ---------------------------------------------------------------------------

def test_queue_full_batch_and_deadline():
    q = serve.MicroBatchQueue(serve.BucketPolicy((64, 128)), microbatch=3,
                              max_wait_s=0.5)
    r1 = q.submit(jnp.zeros((50, 3)), now=0.0)
    r2 = q.submit(jnp.zeros((60, 3)), now=0.1)
    assert r1.bucket == r2.bucket == 64 and q.pending() == 2
    assert q.ready(now=0.4) == []                  # under deadline, partial
    (mb,) = q.ready(now=0.6)                       # oldest waited >= 0.5
    assert mb.deadline_flush and [r.rid for r in mb.requests] == [r1.rid,
                                                                  r2.rid]
    assert q.pending() == 0

    for i in range(4):
        q.submit(jnp.zeros((100, 3)), now=1.0)     # bucket 128
    (full,) = q.ready(now=1.0)                     # full batch, no deadline
    assert full.bucket == 128 and len(full.requests) == 3
    assert not full.deadline_flush and q.pending(128) == 1
    (rest,) = q.drain()
    assert len(rest.requests) == 1 and q.pending() == 0


# ---------------------------------------------------------------------------
# Engine: one shared engine (module scope) keeps compile cost bounded.
# ---------------------------------------------------------------------------

CLOCK = FakeClock()


@pytest.fixture(scope="module")
def engine():
    cfg = serve.ServeConfig(buckets=(64, 128), microbatch=2, max_wait_s=1.0,
                            variant="pointnet2", task="cls", th=32,
                            impl="xla")
    eng = serve.ServeEngine(cfg, clock=CLOCK)
    eng.warm()
    return eng


def cloud(n, step=0):
    pts, _ = synthetic.classification_batch(0, step, 1, n)
    return pts[0]


def test_mixed_stream_one_compile_per_bucket_impl(engine):
    """n drawn from 4 sizes across 2 buckets: exactly one trace per
    (bucket, impl) executable and per (bucket, th, strategy) plan."""
    sizes = [50, 64, 100, 128, 40, 120]
    rids = [engine.submit(cloud(n, i), now=CLOCK()) for i, n in
            enumerate(sizes)]
    engine.step()
    engine.flush()
    for rid in rids:
        assert engine.results[rid].shape == (engine.cfg.num_classes,)
    traces = engine.plans.traces
    assert sorted(k[1] for k in traces if k[0] == "serve") == [64, 128]
    assert sorted(k[1] for k in traces if k[0] == "plan") == [64, 128]
    assert all(v == 1 for v in traces.values()), dict(traces)


def test_plan_cache_hit_on_second_request(engine):
    hits0 = sum(engine.plans.hits.values())
    traces0 = dict(engine.plans.traces)
    engine.submit(cloud(60), now=CLOCK())
    engine.submit(cloud(64), now=CLOCK())
    engine.step()
    assert sum(engine.plans.hits.values()) > hits0      # warm executables
    assert dict(engine.plans.traces) == traces0         # ... no new traces


def test_deadline_flush_partial_microbatch(engine):
    """One pending request (microbatch=2) dispatches only once its
    deadline passes; the padded partial batch reuses the executable."""
    traces0 = dict(engine.plans.traces)
    CLOCK.t = 100.0
    rid = engine.submit(cloud(50, step=7), now=CLOCK())
    assert engine.step() == []                  # partial, deadline not hit
    CLOCK.t = 100.5
    assert engine.step() == []
    CLOCK.t = 101.25                            # waited 1.25 >= 1.0
    assert engine.step() == [rid]
    assert dict(engine.plans.traces) == traces0  # pad slots, same shapes
    lat, _ = engine._lat[64][-1]
    assert lat == pytest.approx(1.25)
    # the padded forward equals a fresh direct forward of the same cloud
    pc, pv = kops.pad_points(jnp.asarray(cloud(50, step=7)), 64)
    direct = np.asarray(pnn.apply(engine.params, engine._model_cfg(64), pc,
                                  valid=pv))
    np.testing.assert_allclose(engine.results[rid], direct, rtol=1e-5,
                               atol=1e-5)
    # pop-on-read: take() hands the result over exactly once
    assert engine.take(rid) is not None and engine.take(rid) is None


def test_stats_report_percentiles_and_throughput(engine):
    st = engine.stats()
    assert st["impl"] == "xla" and st["served"] >= 9
    for b in (64, 128):
        row = st["buckets"][b]
        assert row["p50_ms"] <= row["p95_ms"] <= row["p99_ms"]
        assert row["count"] > 0 and row["compile_s"] > 0
    assert st["clouds_per_s"] > 0 and st["mpts_per_s"] > 0
    assert st["plan_cache"]["executables"] == 4


def test_impl_is_part_of_the_executable_key():
    """A pallas engine compiles its own (bucket, "pallas") executable,
    once, with the impl pinned at construction (not read per call)."""
    cfg = serve.ServeConfig(buckets=(64,), microbatch=1, max_wait_s=0.0,
                            variant="pointnet2", task="cls", th=32,
                            impl="pallas")
    eng = serve.ServeEngine(cfg)
    for i, n in enumerate([48, 64]):
        eng.submit(cloud(n, i))
        eng.step()
    assert ("serve", 64, "pallas") in eng.plans
    assert all(v == 1 for v in eng.plans.traces.values())
    assert eng.results[0].shape == (cfg.num_classes,)


def test_injected_clock_latencies_exact():
    """Clock-domain regression: latencies and wall_s must live entirely in
    the caller's injected clock domain — _execute used to stamp t_done
    from the engine's real clock even when submit/step carried ``now``,
    mixing domains whenever a logical clock was injected."""
    cfg = serve.ServeConfig(buckets=(64,), microbatch=2, max_wait_s=1.0,
                            variant="pointnet2", task="cls", th=32,
                            impl="xla")
    eng = serve.ServeEngine(cfg)   # default (real) clock, never consulted
    eng.warm()
    r1 = eng.submit(cloud(40, 0), now=10.0)
    r2 = eng.submit(cloud(64, 1), now=10.5)
    assert sorted(eng.step(now=12.0)) == [r1, r2]     # full batch
    st = eng.stats()
    row = st["buckets"][64]
    assert row["p50_ms"] == pytest.approx(1.75e3)     # (2.0 + 1.5) / 2
    assert row["p99_ms"] == pytest.approx(2.0e3 - 0.25e3 * 0.02)
    assert st["wall_s"] == pytest.approx(2.0)         # 12.0 - 10.0
    assert st["clouds_per_s"] == pytest.approx(1.0)

    # flush(now=) threads the injected time the same way
    r3 = eng.submit(cloud(50, 2), now=20.0)
    assert eng.flush(now=23.0) == [r3]
    lat, _ = eng._lat[64][-1]
    assert lat == pytest.approx(3.0)
    assert eng.stats()["wall_s"] == pytest.approx(13.0)


def test_throughput_none_until_first_completion():
    """A submit-only stream has no completed window: stats() must report
    None throughput rather than dividing by the 1e-9 clamp (which turned
    an idle engine into an absurd clouds/s figure)."""
    cfg = serve.ServeConfig(buckets=(64,), microbatch=4, max_wait_s=60.0,
                            variant="pointnet2", task="cls", th=32,
                            impl="xla")
    eng = serve.ServeEngine(cfg, clock=FakeClock(5.0))
    assert eng.stats()["clouds_per_s"] is None        # nothing at all
    eng.submit(cloud(40))
    assert eng.step() == []                           # partial, no deadline
    st = eng.stats()
    assert st["wall_s"] is None
    assert st["clouds_per_s"] is None and st["mpts_per_s"] is None
    assert st["buckets"] == {}
    # a zero-width window (batch completed at the instant of its submit,
    # injected clock) is still "unknown", not a clamp-divided absurdity
    eng.flush(now=5.0)
    st = eng.stats()
    assert st["served"] == 1 and st["clouds_per_s"] is None
    assert st["wall_s"] is None
    # once the window has width the numbers come back
    eng.submit(cloud(30), now=5.5)
    assert eng.flush(now=6.0) != []
    st = eng.stats()
    assert st["clouds_per_s"] == pytest.approx(2.0)   # 2 clouds / 1.0 s
    assert st["buckets"][64]["clouds_per_s"] == st["clouds_per_s"]


def test_mesh_dispatch_matches_single_device():
    """mesh="auto" (elastic mesh over host devices, fit_specs-fitted
    microbatch sharding) returns the same logits as the mesh-free path."""
    kw = dict(buckets=(64,), microbatch=2, max_wait_s=0.0,
              variant="pointnet2", task="cls", th=32, impl="xla")
    eng_m = serve.ServeEngine(serve.ServeConfig(mesh="auto", **kw))
    eng_s = serve.ServeEngine(serve.ServeConfig(**kw))
    assert eng_m.mesh is not None
    for eng in (eng_m, eng_s):
        for i, n in enumerate([40, 64, 50]):
            eng.submit(cloud(n, i))
            eng.step()
        eng.flush()
    for rid in eng_s.results:
        np.testing.assert_allclose(eng_m.results[rid], eng_s.results[rid],
                                   rtol=1e-5, atol=1e-5)


MESH_PALLAS_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import numpy as np
    from repro import serve
    from repro.data import synthetic

    kw = dict(buckets=(64,), microbatch=4, max_wait_s=0.0,
              variant="pointnet2", task="cls", th=32, impl="pallas")
    eng_m = serve.ServeEngine(serve.ServeConfig(mesh="auto", **kw))
    eng_s = serve.ServeEngine(serve.ServeConfig(**kw), params=eng_m.params)
    for eng in (eng_m, eng_s):
        for i, n in enumerate([40, 64, 50, 30, 60]):
            eng.submit(synthetic.classification_batch(0, i, 1, n)[0][0])
            eng.step()
        eng.flush()
    err = max(float(np.max(np.abs(eng_m.results[r] - eng_s.results[r])))
              for r in eng_s.results)
    out = eng_m.forward(64, *eng_m._filler(64))
    shards = sorted((s.device.id, s.data.shape[0])
                    for s in out.addressable_shards)
    print(json.dumps({"n_dev": int(eng_m.mesh.devices.size), "err": err,
                      "shards": shards, "batch": out.shape[0]}))
""")


def test_mesh_dispatch_pallas_matches_single_device():
    """impl="pallas" on a multi-device mesh: XLA cannot partition a Mosaic
    kernel, so each device runs the model on its own clouds inside a
    shard_map (models/pnn.apply_batch) — every device holds one cloud of
    the microbatch, and the mesh logits equal the single-device ones.
    Four host devices, so a subprocess."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", MESH_PALLAS_PROG],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    assert data["n_dev"] == 4
    assert data["err"] <= 1e-5
    assert data["batch"] == 4
    assert data["shards"] == [[d, 1] for d in range(4)]
