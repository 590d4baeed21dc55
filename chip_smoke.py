"""Smoke run of the main path on a TPU: serving, scene inference, fine-tuning.

    python chip_smoke.py                # one chip: kernels, serve, scene,
                                        # fine-tune
    python chip_smoke.py --four-chips   # four chips: mesh serve + mesh step

Everything runs in this one process (a chip belongs to one process), with
the Pallas kernels compiled for the chip (``impl="pallas"``) and checked
against the jnp oracle path (``impl="xla"``) on the same parameters and
inputs: the point ops' indices exactly, the models' outputs to the
rounding of their matmuls.  Each phase prints one line: its shapes,
compile seconds and count, peak host memory, and its error against the
reference.  Times here are smoke timings, not performance numbers.  Any
failed check raises, which exits non-zero; the last line of a passing run
is one JSON object naming the device.

There is no CPU fallback: without a TPU the script exits non-zero before
any phase runs.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import ctypes
import dataclasses
import functools
import gc
import json
import os
import resource
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

# Tolerances.  The kernel phase holds the Pallas point ops to the jnp
# oracle exactly: both sum squared coordinate differences in one order
# and break ties to the lowest index, so every FPS, ball-query and kNN
# index and count must agree bit for bit, and the one-hot gather (asked
# for at HIGHEST precision) must fetch its rows exactly.
INTERP_RTOL = 1e-6       # interpolated features, a few f32 roundings
# The other phases compare whole models against the xla path run at full
# f32 matmul precision.  On the pallas side the model's matmuls run at the
# chip's default precision, one bf16 pass (unit roundoff 2^-9 ~ 2e-3 per
# dense layer, 12 dense layers in the seg network), so they differ by
# rounding alone, and the limits sit a little above the errors measured on
# a TPU v5 lite (logits 1.7e-2, labels 0.9918, first loss 6.6e-4).
LOGIT_RTOL = 4e-2        # max |pallas - xla| / max |xla|, serve phase
LABEL_AGREE = 0.98       # share of scene points with the same argmax
LOSS_RTOL = 2e-3         # first fine-tune step, |pallas - xla| / xla
# The mesh and the single device run the same program at the same
# precision; only the order of f32 sums differs.
MESH_RTOL = 1e-3

_compiles = {"n": 0, "s": 0.0}


def _count_compiles(event, duration_secs, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _compiles["n"] += 1
        _compiles["s"] += duration_secs


def _release_host_memory():
    """Drop the finished phase's executables and hand freed memory back to
    the OS.  Each TPU compile of a whole model takes ~5 GB of host memory,
    and the phases compile on threads, whose glibc arenas keep what they
    freed; without this, one process running every phase outgrows a 40 GiB
    host."""
    jax.clear_caches()
    gc.collect()
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):    # not glibc: nothing to trim
        pass


class Phase:
    """Times one phase, counts the backend compiles inside it, and releases
    its host memory at the end."""

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.n0, self.s0 = _compiles["n"], _compiles["s"]
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        self.wall_s = time.monotonic() - self.t0
        self.compiles = _compiles["n"] - self.n0
        self.compile_s = _compiles["s"] - self.s0
        self.peak_rss_gb = resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss / 2**20
        _release_host_memory()
        return False

    def report(self, shapes, **errors):
        errs = " ".join(f"{k}={v:.3e}" for k, v in errors.items())
        print(f"[{self.name}] shapes={shapes} compile_s={self.compile_s:.1f} "
              f"compiles={self.compiles} smoke_wall_s={self.wall_s:.1f} "
              f"host_peak_rss_gb={self.peak_rss_gb:.1f} {errs}", flush=True)


def _check(ok, what):
    if not ok:
        raise AssertionError(what)


def _rel_err(a, b):
    return float(np.max(np.abs(a - b)) / max(float(np.max(np.abs(b))), 1e-6))


def _parallel(*jobs):
    """Run (fn, precision) jobs on threads and return their results in
    order.  XLA compiles without holding the GIL, so the two sides' compiles
    overlap on the host's cores; the precision context is thread-local and
    is entered inside each job."""
    def run(fn, precision):
        with jax.default_matmul_precision(precision):
            return fn()

    with concurrent.futures.ThreadPoolExecutor(len(jobs)) as ex:
        futs = [ex.submit(run, fn, prec) for fn, prec in jobs]
        return [f.result() for f in futs]


def _serve_all(eng, clouds, precision=None):
    """Submit every cloud, stepping after each; returns results in order."""
    with jax.default_matmul_precision(precision):
        rids = []
        for c in clouds:
            rids.append(eng.submit(c))
            eng.step()
        eng.flush()
    return [eng.take(r) for r in rids]


def kernel_phase(n, *, seed=0):
    """The point ops of the default serving model — per SA stage the
    block-wise FPS and ball query, and the FP stage's kNN + gather
    interpolation, at the serving block sizes (th-point leaves; the
    cloud's size sets only how many) — on one seeded n-point cloud, with
    the Pallas kernels and with the jnp oracle, each stage on one shared
    partition.  Returns ({name: mismatching entries} over the index
    outputs, the interpolated features' max relative error)."""
    from repro import core, serve
    from repro.data import synthetic
    from repro.models import pnn

    scfg = serve.ServeConfig()
    mcfg = pnn.PNNConfig(variant=scfg.variant, task=scfg.task, n_points=n,
                         point_ops="bppo", th=scfg.th, strategy=scfg.strategy)
    th, sizes = mcfg.th, mcfg.stage_sizes()
    wc = max(16, int(2 * th * mcfg.stages[0].rate))   # as pnn's FP stages
    partition = jax.jit(lambda c, v: core.partition(
        c, v, th=th, strategy=mcfg.strategy, on_overflow="silent"))

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def point_ops(part, i, impl):
        s = mcfg.stages[i]
        samp = core.blockwise_fps(part, rate=s.rate, k_out=sizes[i + 1],
                                  bs=th, impl=impl)
        nb = core.blockwise_ball_query(part, samp, radius=s.radius,
                                       num=s.nsample, w=2 * th, impl=impl)
        feats, idx3, _ = core.blockwise_interpolate(
            part, samp, samp.coords, wc=wc, bs=th, impl=impl)
        return {"fps": samp.idx, "ball": nb.idx, "count": nb.cnt,
                "knn": idx3, "interp": feats, "coords": samp.coords,
                "valid": samp.valid}

    coords = jnp.asarray(synthetic.scene(seed, n)[0])
    valid = jnp.ones((n,), bool)
    mismatch, err = {}, 0.0
    for i in range(len(mcfg.stages)):
        part = partition(coords, valid)
        got, want = jax.device_get(_parallel(
            (lambda: point_ops(part, i, "pallas"), None),
            (lambda: point_ops(part, i, "xla"), None)))
        for k in ("fps", "ball", "count", "knn"):
            mismatch[f"{k}{i}"] = int(np.sum(got[k] != want[k]))
        err = max(err, _rel_err(got["interp"], want["interp"]))
        coords, valid = jnp.asarray(want["coords"]), jnp.asarray(want["valid"])
    return mismatch, err


def serve_phase(sizes, *, buckets=None, th=None, mesh="none", ref_mesh=None,
                seed=0):
    """Warm a pallas ServeEngine, serve ``sizes`` seeded clouds, compare with
    a reference engine on the same params.  Returns (engine, max error)."""
    from repro import serve
    from repro.data import synthetic

    cfg = serve.ServeConfig(impl="pallas", mesh=mesh)
    if buckets is not None:
        cfg = dataclasses.replace(cfg, buckets=tuple(buckets))
    if th is not None:
        cfg = dataclasses.replace(cfg, th=th)
    eng = serve.ServeEngine(cfg, seed=seed)
    if ref_mesh is None:        # one chip: the xla oracle at f32 precision
        ref_cfg, ref_prec = dataclasses.replace(cfg, impl="xla"), "highest"
    else:                       # four chips: the same engine on one device
        ref_cfg, ref_prec = dataclasses.replace(cfg, mesh=ref_mesh), None
    ref = serve.ServeEngine(ref_cfg, params=eng.params)
    _parallel((eng.warm, None), (ref.warm, ref_prec))
    traces = dict(eng.plans.stats()["traces"])

    clouds = [synthetic.scene(seed + i, n)[0] for i, n in enumerate(sizes)]
    got = _serve_all(eng, clouds)
    want = _serve_all(ref, clouds, ref_prec)
    _check(eng.plans.stats()["traces"] == traces,
           f"traces after warm-up: {eng.plans.stats()['traces']} vs {traces}")
    err = 0.0
    for n, g, w in zip(sizes, got, want):
        _check(g.shape == (n, cfg.num_classes), f"result shape {g.shape}")
        _check(np.isfinite(g).all(), f"non-finite logits for n={n}")
        err = max(err, _rel_err(g, w))
    return eng, err


def scene_phase(n, *, tile_points=4096, th=256, seed=0):
    """Stream one seeded n-point scene through pallas and xla SceneEngines
    (same params); returns (logits, share of agreeing labels)."""
    from repro import scene
    from repro.data import synthetic

    coords, _ = synthetic.scene(seed, n)
    cfg = scene.SceneConfig(tile_points=tile_points, th=th, impl="pallas")
    eng = scene.SceneEngine(cfg, seed=seed)
    ref = scene.SceneEngine(dataclasses.replace(cfg, impl="xla"),
                            params=eng.params)
    _parallel((eng.warm, None), (ref.warm, "highest"))
    logits, plan = eng.infer(coords)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.infer(coords, plan=plan)
    _check(logits.shape == (n, cfg.num_classes), f"shape {logits.shape}")
    _check(np.isfinite(logits).all(), "non-finite scene logits")
    agree = float(np.mean(logits.argmax(-1) == want.argmax(-1)))
    return logits, plan, agree


def train_phase(*, n_points, batch, steps, th=256, mesh="none",
                ref_mesh=None):
    """Fine-tune pointnet2_seg with pallas kernels from seeded params.
    Returns (per-step losses, the first batch's loss after the last step —
    on one device only, None on a mesh — and the reference's loss of the
    first batch at the initial params, i.e. its first-step loss).  Each
    step draws a new batch, so the per-step losses wander with the data;
    the first batch scored again after training is the like-for-like
    measure of whether the loss fell."""
    from repro.data import synthetic
    from repro.models import pnn
    from repro.train import pnn as tpnn

    cfg = tpnn.TrainConfig(preset="pointnet2_seg", n_points=n_points, th=th,
                           impl="pallas", batch=batch, steps=steps, lr=1e-3,
                           mesh=mesh)
    mcfg = tpnn.model_config(cfg)
    params0 = pnn.init(jax.random.PRNGKey(cfg.seed), mcfg)
    # fit()'s first batch (its batches are a pure function of seed, step).
    pts, labels = synthetic.segmentation_batch(cfg.seed + 11, 0, batch,
                                               n_points)

    def batch0_loss(model_cfg, params):
        return float(jax.jit(lambda p, b: tpnn.loss_fn(p, model_cfg, b)[0])(
            params, {"points": pts, "labels": labels}))

    if ref_mesh is None:        # one chip: the xla oracle at f32 precision
        ref_cfg = tpnn.model_config(dataclasses.replace(cfg, impl="xla"))
        prec = "highest"
    else:                       # four chips: the same model on one device
        ref_cfg, prec = mcfg, None
    (params, _, info), ref0 = _parallel(
        (lambda: tpnn.fit(cfg, params=params0, log=lambda *a, **k: None),
         None),
        (lambda: batch0_loss(ref_cfg, params0), prec))
    after = batch0_loss(mcfg, params) if mesh == "none" else None
    return [float(h["loss"]) for h in info["history"]], after, ref0


def _one_chip(out):
    with Phase("kernels") as ph:
        mismatch, err = kernel_phase(4096)
    ph.report("n=4096 th=256 stages=2 ops=fps,ball_query,knn,gather "
              f"mismatches={mismatch}", interp_rel_err=err)
    _check(not any(mismatch.values()), f"pallas indices differ: {mismatch}")
    _check(err <= INTERP_RTOL, f"interpolation off by {err} > {INTERP_RTOL}")
    out["kernels"] = {"mismatch": mismatch, "interp_rel_err": err}

    serve_sizes = [3000, 4096, 1500, 12000, 16384, 9000, 30000, 65536,
                   50000, 2048, 14000, 40000]
    with Phase("serve") as ph:
        eng, err = serve_phase(serve_sizes)
        bucket, n0 = eng.policy.buckets[0], _compiles["n"]
        kernels = eng.executable(bucket).as_text().count("tpu_custom_call")
        shapes = (f"buckets={eng.policy.buckets} microbatch="
                  f"{eng.queue.microbatch} th={eng.cfg.th} "
                  f"requests={serve_sizes} hlo_bucket={bucket} "
                  f"hlo_compiles={_compiles['n'] - n0}")
        del eng
    ph.report(shapes, logit_rel_err=err, tpu_custom_calls=kernels)
    _check(err <= LOGIT_RTOL, f"serve logits off by {err} > {LOGIT_RTOL}")
    _check(kernels > 0, "serve executable holds no tpu_custom_call")
    out["serve"] = {"logit_rel_err": err, "tpu_custom_calls": kernels}

    with Phase("scene") as ph:
        logits, plan, agree = scene_phase(262_144)
    ph.report(f"n={plan.n} tiles={plan.num_tiles} "
              f"max_tile={plan.max_tile_n} logits={logits.shape}",
              label_agree=agree)
    _check(agree >= LABEL_AGREE, f"scene labels agree on {agree}")
    out["scene"] = {"label_agree": agree, "tiles": plan.num_tiles}

    with Phase("fine-tune") as ph:
        losses, after, ref0 = train_phase(n_points=8192, batch=8, steps=6)
    rel = abs(losses[0] - ref0) / abs(ref0)
    ph.report("preset=pointnet2_seg n_points=8192 batch=8 th=256 "
              f"losses={[round(x, 4) for x in losses]} batch0_after="
              f"{after:.4f} xla_loss0={ref0:.4f}", loss0_rel_err=rel)
    _check(np.isfinite(losses + [after]).all(), f"non-finite loss {losses}")
    _check(after < losses[0], f"batch-0 loss did not fall: {losses[0]} -> "
           f"{after}")
    _check(rel <= LOSS_RTOL, f"first loss off by {rel} > {LOSS_RTOL}")
    out["fine_tune"] = {"losses": losses, "batch0_after": after,
                        "xla_loss0": ref0}


def _four_chips(out):
    # The smallest bucket only: four chips cost four times as much per
    # second, and the larger buckets add compile time, not mesh coverage.
    with Phase("mesh-serve") as ph:
        eng, err = serve_phase([3000, 4096, 1500, 2048, 4000, 3500],
                               buckets=(4096,), mesh="auto",
                               ref_mesh="none")
        # Each device computes the logits of its own clouds: every device
        # holds a shard, and each shard is smaller than the microbatch.
        mesh, b, mb = eng.mesh, eng.policy.buckets[0], eng.queue.microbatch
        logits = eng.forward(b, jnp.zeros((mb, b, 3), jnp.float32),
                             jnp.zeros((mb, b), bool),
                             jnp.zeros((mb,), jnp.int32))
        shards = {s.device: s.data.shape for s in logits.addressable_shards}
        del eng, logits
    ph.report(f"mesh={dict(mesh.shape)} buckets=({b},) logits_per_device="
              f"{sorted(set(shards.values()))}",
              logit_rel_err_vs_one_device=err)
    _check(err <= MESH_RTOL, f"mesh logits off by {err} > {MESH_RTOL}")
    _check(set(shards) == set(jax.devices()), f"logits on {set(shards)}")
    _check(all(sh[0] < mb for sh in shards.values()),
           f"a device holds the whole microbatch: {shards}")

    with Phase("mesh-train") as ph:
        losses, _, ref0 = train_phase(n_points=8192, batch=8, steps=1,
                                      mesh="auto", ref_mesh="none")
    rel = abs(losses[0] - ref0) / abs(ref0)
    ph.report("preset=pointnet2_seg n_points=8192 batch=8 mesh=auto "
              f"loss={losses[0]:.5f} one_device={ref0:.5f}",
              loss_rel_err=rel)
    _check(rel <= MESH_RTOL, f"mesh loss off by {rel} > {MESH_RTOL}")
    out["mesh"] = {"serve_rel_err": err, "train_loss_rel_err": rel,
                   "shape": dict(mesh.shape)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh serve and mesh train phases")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r} "
              f"devices only", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devices)}",
              file=sys.stderr)
        return 2

    from repro import compile_cache
    cache = compile_cache.enable()
    jax.monitoring.register_event_duration_secs_listener(_count_compiles)
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} compile_cache={cache}", flush=True)

    out = {}
    (_four_chips if args.four_chips else _one_chip)(out)
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    name = "chip_smoke_4.json" if args.four_chips else "chip_smoke.json"
    with open(os.path.join(REPO, "chiprun_out", name), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
