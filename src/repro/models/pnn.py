"""Point-based neural networks (paper Table I workloads) in functional JAX.

Backbone = Abstraction stages (point ops + feature MLPs) and, for
segmentation, Propagation stages with skip connections (paper Fig. 2d).
Point operations are selectable:

* ``point_ops="global"`` — the PointAcc-style O(n^2) baseline (core/ref.py);
* ``point_ops="bppo"``   — Fractal partition + block-parallel ops (the
                           paper's contribution, core/bppo.py).

With ``point_ops="bppo"`` the execute phase of every point op additionally
dispatches through the kernel backend selected by ``PNNConfig.impl``:
``"xla"`` (jnp oracle) or ``"pallas"`` (TPU kernels, interpreted on CPU);
``None`` resolves from ``$REPRO_POINT_IMPL``.  Both backends differentiate
(kernels/vjp.py), so either is valid under ``jax.grad`` — training no
longer needs to wrap the model with ``impl="xla"``.  See docs/DESIGN.md §4
and ``train/pnn.py`` for the fine-tune loop.

Variants (simplified but structurally faithful; see docs/DESIGN.md §8):
* ``pointnet2``   — SA = group -> shared MLP -> max-pool.
* ``pointnext``   — SA + inverted-residual MLP blocks after aggregation.
* ``pointvector`` — SA with learned per-neighbor vector gating before pool.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import core
from repro.core import ref
from repro.dist import logical
from repro.kernels import ops as kops

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class SAStage:
    rate: float          # sampling rate (paper: one fixed rate per stage)
    radius: float
    nsample: int
    widths: tuple        # MLP widths applied to grouped features


@dataclasses.dataclass(frozen=True)
class PNNConfig:
    name: str = "pointnet2"
    variant: str = "pointnet2"       # pointnet2 | pointnext | pointvector
    task: str = "cls"                # cls | seg
    num_classes: int = 6
    n_points: int = 1024
    in_channels: int = 3
    stages: tuple = (
        SAStage(0.25, 0.2, 16, (32, 32, 64)),
        SAStage(0.25, 0.4, 16, (64, 64, 128)),
    )
    fp_widths: tuple = ((128, 64), (64, 64))   # seg only, reversed order
    head_widths: tuple = (128,)
    point_ops: str = "global"        # global | bppo
    impl: str | None = None          # bppo execute backend: xla | pallas |
                                     # None ($REPRO_POINT_IMPL, then xla)
    th: int = 64                     # Fractal threshold (paper: 64 cls /
                                     # 256 seg at full scale)
    strategy: str = "fractal"        # partition strategy, every stage
                                     # (core/fractal.py STRATEGIES)
    num_blocks: int = 1              # extra residual blocks (pointnext)
    leaf_chunk: int | None = None    # leaves per lax.map step (large scale)

    def stage_sizes(self):
        sizes = [self.n_points]
        for s in self.stages:
            sizes.append(max(1, int(round(sizes[-1] * s.rate))))
        return sizes


# ---------------------------------------------------------------------------
# Tiny functional NN helpers (params are nested dicts of arrays).
# ---------------------------------------------------------------------------

def _dense_init(key, din, dout):
    k1, _ = jax.random.split(key)
    w = jax.random.normal(k1, (din, dout)) * (2.0 / (din + dout)) ** 0.5
    return {"w": w.astype(jnp.float32), "b": jnp.zeros((dout,), jnp.float32)}


def _dense(p, x):
    return x @ p["w"] + p["b"]


def _ln_init(d):
    return {"g": jnp.ones((d,), jnp.float32), "b": jnp.zeros((d,), jnp.float32)}


def _ln(p, x, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.var(x, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["g"] + p["b"]


def _mlp_init(key, din, widths):
    params = []
    for w in widths:
        key, sub = jax.random.split(key)
        params.append({"dense": _dense_init(sub, din, w), "ln": _ln_init(w)})
        din = w
    return params


def _mlp(params, x):
    for p in params:
        x = jax.nn.relu(_ln(p["ln"], _dense(p["dense"], x)))
    return x


# ---------------------------------------------------------------------------
# Point-op plumbing: one stage of sampling + grouping in either mode.
# ---------------------------------------------------------------------------

def _stage_points(cfg: PNNConfig, stage: SAStage, coords, feats, valid,
                  n_out, part=None):
    """Returns (new_coords (n_out,3), grouped (n_out, nsample, C+3),
    gmask, new_valid, ctx) running one sampling+grouping+gathering round.

    ``ctx`` carries what propagation needs (partition/samples for bppo,
    nothing for global).  ``part`` optionally supplies a precomputed
    FractalPartition of (coords, valid) — the serving plan cache
    (docs/DESIGN.md §9) partitions once per shape bucket and passes the
    plan in, so only the execute phase runs per request batch."""
    n = coords.shape[0]
    if cfg.point_ops == "global":
        sidx, svalid = ref.fps(coords, valid, n_out)
        centers = coords[sidx]
        nidx, cnt = ref.ball_query(coords, valid, centers, svalid,
                                   stage.radius, stage.nsample)
        gmask = (jnp.arange(stage.nsample)[None, :] <
                 jnp.minimum(cnt, stage.nsample)[:, None])
        gmask = gmask & svalid[:, None]
        gmask = gmask.at[:, 0].set(svalid)  # nearest pad always present
        rel = coords[nidx] - centers[:, None, :]
        gfeats = jnp.concatenate([rel, feats[nidx]], axis=-1)
        ctx = {"mode": "global", "coords": coords, "centers": centers,
               "svalid": svalid}
        return centers, gfeats, gmask, svalid, ctx

    if part is None:
        # Silent: this partition sits inside every jitted forward
        # (training steps and the deeper SA stages of serving) — no host
        # callback there.  Overflow is surfaced at the plan boundaries:
        # partition's own default ("warn"), the serve plan executable
        # (ServeConfig.on_overflow), the scene tiler, and check_overflow.
        part = core.partition(coords, valid, th=cfg.th,
                              strategy=cfg.strategy, on_overflow="silent")
    samp = core.blockwise_fps(part, rate=stage.rate, k_out=n_out, bs=cfg.th,
                              impl=cfg.impl)
    nb = core.blockwise_ball_query(part, samp, radius=stage.radius,
                                   num=stage.nsample, w=2 * cfg.th,
                                   chunk=cfg.leaf_chunk, impl=cfg.impl)
    feats_sorted = feats[part.perm]
    centers = samp.coords
    rel = core.gather(part.coords, nb.idx) - centers[:, None, :]
    gmask = nb.mask
    gmask = gmask.at[:, 0].set(samp.valid)
    gfeats = jnp.concatenate([rel, core.gather(feats_sorted, nb.idx)],
                             axis=-1)
    ctx = {"mode": "bppo", "part": part, "samp": samp}
    return centers, gfeats, gmask, samp.valid, ctx


def _propagate(cfg: PNNConfig, ctx, coarse_feats, fine_feats, fine_valid):
    """FP stage: interpolate coarse feats onto the fine cloud (3-NN IDW)."""
    if ctx["mode"] == "global":
        out, _, _ = ref.interpolate_3nn(
            ctx["coords"], ctx["centers"], ctx["svalid"], coarse_feats)
        return jnp.concatenate([out, fine_feats], axis=-1)
    part, samp = ctx["part"], ctx["samp"]
    wc = max(16, int(2 * cfg.th * cfg.stages[0].rate))
    out_sorted, _, _ = core.blockwise_interpolate(
        part, samp, coarse_feats, wc=wc, bs=cfg.th, chunk=cfg.leaf_chunk,
        impl=cfg.impl)
    fine_sorted = fine_feats[part.perm]
    merged = jnp.concatenate([out_sorted, fine_sorted], axis=-1)
    # back to the fine cloud's original order
    n = part.n
    inv = jnp.zeros((n,), jnp.int32).at[part.perm].set(
        jnp.arange(n, dtype=jnp.int32))
    return merged[inv]


# ---------------------------------------------------------------------------
# Model init / apply.
# ---------------------------------------------------------------------------

def init(key, cfg: PNNConfig):
    params = {"stages": [], "fp": [], "head": []}
    sizes = cfg.stage_sizes()
    c_in = cfg.in_channels
    for i, s in enumerate(cfg.stages):
        key, k1, k2, k3 = jax.random.split(key, 4)
        stage_p = {"mlp": _mlp_init(k1, c_in + 3, s.widths)}
        if cfg.variant == "pointvector":
            stage_p["vec"] = _dense_init(k2, c_in + 3, s.widths[-1])
        if cfg.variant == "pointnext":
            blocks = []
            for _ in range(cfg.num_blocks):
                key, kb = jax.random.split(key)
                blocks.append(_mlp_init(kb, s.widths[-1],
                                        (2 * s.widths[-1], s.widths[-1])))
            stage_p["res"] = blocks
        params["stages"].append(stage_p)
        c_in = s.widths[-1]
    if cfg.task == "seg":
        skip_dims = [cfg.in_channels] + \
            [s.widths[-1] for s in cfg.stages[:-1]]
        up_dim = cfg.stages[-1].widths[-1]
        for i, widths in enumerate(cfg.fp_widths):
            key, kf = jax.random.split(key)
            din = up_dim + skip_dims[-(i + 1)]
            params["fp"].append(_mlp_init(kf, din, widths))
            up_dim = widths[-1]
        head_in = up_dim
    else:
        head_in = cfg.stages[-1].widths[-1]
    key, kh, ko = jax.random.split(key, 3)
    params["head"] = _mlp_init(kh, head_in, cfg.head_widths)
    params["out"] = _dense_init(ko, cfg.head_widths[-1], cfg.num_classes)
    return params


def _aggregate(cfg, stage_p, gfeats, gmask, variant):
    h = _mlp(stage_p["mlp"], gfeats)                     # (m, ns, C')
    if variant == "pointvector":
        gate = jax.nn.sigmoid(_dense(stage_p["vec"], gfeats))
        h = h * gate
    h = jnp.where(gmask[..., None], h, -3.0e38)
    pooled = jnp.max(h, axis=-2)
    pooled = jnp.where(gmask.any(-1, keepdims=True), pooled, 0.0)
    if variant == "pointnext":
        for blk in stage_p["res"]:
            pooled = pooled + _mlp(blk, pooled)
    return pooled


def apply(params, cfg: PNNConfig, coords: Array, feats: Array | None = None,
          valid: Array | None = None, part0=None):
    """Single-cloud forward (vmap for batches).

    cls: returns (num_classes,) logits.
    seg: returns (n, num_classes) per-point logits.

    ``part0`` optionally injects a precomputed stage-0 FractalPartition of
    (coords, valid) (bppo only; ignored for global ops) — the serving plan
    cache builds it once per shape bucket (docs/DESIGN.md §9).
    """
    n = coords.shape[0]
    if valid is None:
        valid = jnp.ones((n,), bool)
    if feats is None:
        feats = coords
    sizes = cfg.stage_sizes()
    skips = [(coords, feats, valid)]
    ctxs = []
    for i, s in enumerate(cfg.stages):
        centers, gfeats, gmask, svalid, ctx = _stage_points(
            cfg, s, skips[-1][0], skips[-1][1], skips[-1][2], sizes[i + 1],
            part=part0 if i == 0 else None)
        pooled = _aggregate(cfg, params["stages"][i], gfeats, gmask,
                            cfg.variant)
        ctxs.append(ctx)
        skips.append((centers, pooled, svalid))

    if cfg.task == "cls":
        _, f, v = skips[-1]
        f = jnp.where(v[:, None], f, -3.0e38)
        g = jnp.max(f, axis=0)
        h = _mlp(params["head"], g)
        return _dense(params["out"], h)

    up = skips[-1][1]
    for i, widths in enumerate(cfg.fp_widths):
        lvl = len(cfg.stages) - 1 - i
        fine_coords, fine_feats, fine_valid = skips[lvl]
        merged = _propagate(cfg, ctxs[lvl], up, fine_feats, fine_valid)
        up = _mlp(params["fp"][i], merged)
    h = _mlp(params["head"], up)
    return _dense(params["out"], h)


def batch_spec(cfg: PNNConfig, mesh, batch: int):
    """The ``PartitionSpec`` over which ``apply_batch`` splits ``batch``
    clouds on ``mesh``, or None when it leaves placement to the logical
    rules.

    XLA cannot partition a Mosaic kernel, so the Pallas point ops on a
    multi-device mesh run inside a ``shard_map``: each device runs the
    whole model on its own clouds.  The clouds split over every mesh axis,
    in mesh order, while the product of the axis sizes divides ``batch``
    (callers size batches to the mesh; axes left out repeat the work)."""
    if (mesh is None or mesh.devices.size == 1 or cfg.point_ops != "bppo"
            or kops.resolve_impl(cfg.impl, default="xla") != "pallas"):
        return None
    axes, size = [], 1
    for name, n in zip(mesh.axis_names, mesh.devices.shape):
        if batch % (size * n) == 0:
            axes.append(name)
            size *= n
    return P(tuple(axes))


def apply_batch(params, cfg: PNNConfig, coords: Array, valid=None,
                part0=None, mesh=None):
    """``apply`` over a batch of clouds: coords (B, n, 3) -> (B, ...).

    Without a ``batch_spec`` (no mesh, one device, or the xla point ops)
    this is a vmap whose clouds sit on the ``batch`` logical axis, and the
    ``lc`` constraints inside place the rest.  With one, the vmap runs
    inside a ``shard_map`` over the spec, with the params replicated."""
    if valid is None:
        valid = jnp.ones(coords.shape[:2], bool)

    def fwd(params, coords, valid, part0):
        return jax.vmap(lambda c, v, p: apply(params, cfg, c, valid=v,
                                              part0=p))(coords, valid, part0)

    spec = batch_spec(cfg, mesh, coords.shape[0])
    if spec is None:
        coords = logical.lc(coords, "batch", "points", None)
        valid = logical.lc(valid, "batch", "points")
        return fwd(params, coords, valid, part0)

    def body(params, coords, valid, part0):
        with logical.suspended():
            return fwd(params, coords, valid, part0)

    return jax.shard_map(body, mesh=mesh, in_specs=(P(), spec, spec, spec),
                         out_specs=spec, check_vma=False)(
                             params, coords, valid, part0)


# Paper Table I model presets -------------------------------------------------

def pointnet2_cls(n=1024, point_ops="global", th=64, impl=None):
    return PNNConfig(name="pointnet2_cls", variant="pointnet2", task="cls",
                     n_points=n, point_ops=point_ops, th=th, impl=impl)


def pointnext_cls(n=1024, point_ops="global", th=64, impl=None):
    return PNNConfig(name="pointnext_cls", variant="pointnext", task="cls",
                     n_points=n, point_ops=point_ops, th=th, impl=impl)


def pointnet2_seg(n=2048, point_ops="global", th=256, impl=None):
    return PNNConfig(name="pointnet2_seg", variant="pointnet2", task="seg",
                     n_points=n, point_ops=point_ops, th=th, impl=impl)


def pointnext_seg(n=2048, point_ops="global", th=256, impl=None):
    return PNNConfig(name="pointnext_seg", variant="pointnext", task="seg",
                     n_points=n, point_ops=point_ops, th=th, impl=impl)


def pointvector_seg(n=2048, point_ops="global", th=256, impl=None):
    return PNNConfig(name="pointvector_seg", variant="pointvector",
                     task="seg", n_points=n, point_ops=point_ops, th=th,
                     impl=impl)


def scene_seg(n=4096, th=256, impl=None, widths=(32, 32, 64),
              fp=(64, 64), rate=0.25, radius=0.25, nsample=16):
    """Single-SA-stage segmentation config for scene tiling (DESIGN.md §10).

    With exactly one abstraction stage, every point op runs inside the
    stage-0 partition — the one ``apply(part0=...)`` accepts from outside
    — so tile-wise execution over exact fractal subtrees (``repro.scene``
    with ``halo=0`` and per-tile ``dim0``) reproduces the whole-scene
    forward to float precision (tests/test_scene.py).  Multi-stage
    configs re-partition their sampled cloud per tile and are therefore
    approximate at tile borders; the halo ring is the quality knob there.
    """
    return PNNConfig(name="scene_seg", variant="pointnet2", task="seg",
                     n_points=n, point_ops="bppo", th=th, impl=impl,
                     stages=(SAStage(rate, radius, nsample, widths),),
                     fp_widths=(fp,))
