"""PNN fine-tuning: ``jax.value_and_grad`` through the full BPPO pipeline
with either point-op backend.

With the execute-phase VJPs in place (kernels/vjp.py, docs/DESIGN.md §4)
``PNNConfig(impl="pallas")`` is valid under ``jax.grad`` — the kernels run
in the backward pass too (gather's transposed one-hot scatter-add; the
index producers contribute zero cotangents), so training no longer falls
back to the XLA oracle.  The loop reuses the repo's training
infrastructure: ``train/optimizer.py`` (AdamW + clipping),
``train/checkpoint.py`` + ``train/loop.py`` (restore/resume, straggler
monitor), ``data/synthetic.py`` (resumable counter-based batches), and
shards like ``launch/train.py``: clouds -> the ``batch`` logical axis
(``dist.logical.fit_specs``-fitted so non-dividing batch sizes drop),
fractal leaves -> ``model`` via the ``lc`` constraints already inside
``core/bppo.py``.  With the Pallas point ops the clouds split over the
whole mesh instead (``models/pnn.apply_batch``).

CLI (the CI train-smoke leg)::

  PYTHONPATH=src python -m repro.train.pnn --preset pointnet2_cls \
      --steps 4 --impl pallas --mesh auto
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compile_cache
from repro.data import synthetic
from repro.dist import elastic, logical
from repro.kernels import ops as kops
from repro.models import pnn
from repro.train import loop as loop_lib
from repro.train import optimizer as opt_lib

PRESETS = {
    "pointnet2_cls": pnn.pointnet2_cls,
    "pointnext_cls": pnn.pointnext_cls,
    "pointnet2_seg": pnn.pointnet2_seg,
    "pointnext_seg": pnn.pointnext_seg,
    "pointvector_seg": pnn.pointvector_seg,
}


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Fine-tune knobs: model preset + data shape + dispatch + loop."""

    preset: str = "pointnet2_cls"
    n_points: int = 192
    th: int = 32
    point_ops: str = "bppo"          # bppo | global
    impl: str | None = None          # xla | pallas | None ($REPRO_POINT_IMPL)
    batch: int = 8
    steps: int = 20
    lr: float = 3e-3
    weight_decay: float = 0.0
    seed: int = 0
    mesh: str = "none"               # none | auto (elastic host mesh)
    model_axis: int = 2
    leaf_chunk: int | None = None
    ckpt_dir: str = ""
    ckpt_every: int = 50
    grad_compression: str = "none"   # none | bf16 | int8


def model_config(cfg: TrainConfig) -> pnn.PNNConfig:
    # Same default chain as every other entrypoint: explicit arg >
    # $REPRO_POINT_IMPL > the xla oracle.
    mcfg = PRESETS[cfg.preset](n=cfg.n_points, point_ops=cfg.point_ops,
                               th=cfg.th,
                               impl=kops.resolve_impl(cfg.impl,
                                                      default="xla"))
    return dataclasses.replace(mcfg, leaf_chunk=cfg.leaf_chunk)


def loss_fn(params, mcfg: pnn.PNNConfig, batch, mesh=None):
    """Masked cross-entropy over a batch dict {points, labels[, valid]}.

    Returns (loss, aux) with aux = {"acc": ...} so the step metrics carry
    a trainability signal alongside the loss.  ``mesh`` is the mesh the
    step runs on (``pnn.apply_batch``: the Pallas point ops split the
    clouds over it)."""
    pts = batch["points"]
    labels = batch["labels"]
    valid = batch.get("valid")
    if valid is None:
        valid = jnp.ones(pts.shape[:2], bool)
    logits = pnn.apply_batch(params, mcfg, pts, valid, mesh=mesh)
    ll = jax.nn.log_softmax(logits)
    if mcfg.task == "cls":
        picked = jnp.take_along_axis(ll, labels[:, None], axis=-1)
        loss = -jnp.mean(picked)
        acc = jnp.mean(jnp.argmax(logits, -1) == labels)
    else:
        picked = jnp.take_along_axis(ll, labels[..., None], axis=-1)[..., 0]
        denom = jnp.maximum(jnp.sum(valid), 1)
        loss = -jnp.sum(jnp.where(valid, picked, 0.0)) / denom
        hit = (jnp.argmax(logits, -1) == labels) & valid
        acc = jnp.sum(hit) / denom
    return loss, {"acc": acc}


def train_step_fn(mcfg: pnn.PNNConfig, opt_cfg: opt_lib.OptConfig,
                  mesh=None):
    """The raw (unjitted) fine-tune step: value_and_grad + AdamW update.

    Split out so callers that own their own jit (the dry-run train cell
    lowers it with explicit in_shardings) stay in lockstep with the step
    the trainer actually runs."""
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def step(params, opt_state, batch):
        (loss, aux), grads = grad_fn(params, mcfg, batch, mesh)
        params, opt_state, om = opt_lib.update(opt_cfg, grads, opt_state,
                                               params)
        return params, opt_state, {"loss": loss, **aux, **om}

    return step


def make_train_step(mcfg: pnn.PNNConfig, opt_cfg: opt_lib.OptConfig,
                    mesh=None):
    """One jitted AdamW step; ``return_grads=True`` hands raw grads back
    for the loop's gradient-compression / error-feedback path."""
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    step = jax.jit(train_step_fn(mcfg, opt_cfg, mesh))

    @jax.jit
    def grads_only(params, batch):
        (loss, aux), grads = grad_fn(params, mcfg, batch, mesh)
        return grads, {"loss": loss, **aux}

    def train_step(params, opt_state, batch, return_grads=False):
        if return_grads:
            return grads_only(params, batch)
        return step(params, opt_state, batch)

    return train_step


def fit(cfg: TrainConfig, params=None, log=print):
    """Run the fine-tune loop; returns (params, opt_state, info).

    ``info["history"]`` carries per-step loss (the generic loop records
    {step, dt, loss, straggler}); with ``ckpt_dir`` set the loop restores
    the latest step and resumes (the synthetic batch stream is a pure
    function of (seed, step), so a restart reproduces the exact
    stream)."""
    mcfg = model_config(cfg)
    mesh = (elastic.make_mesh(model_axis=cfg.model_axis)
            if cfg.mesh == "auto" else None)
    rules = logical.RULES_V0
    if mesh is not None:
        log(f"[train.pnn] mesh "
            f"{dict(zip(mesh.axis_names, mesh.devices.shape))} "
            f"({mesh.devices.size} devices), impl={mcfg.impl}")

    def init_params():
        p = pnn.init(jax.random.PRNGKey(cfg.seed), mcfg)
        if mesh is not None:
            # PNN params are small: replicate; the point-op leaves shard
            # over "model" via bppo's lc constraints inside the step (the
            # clouds over the whole mesh, with the Pallas point ops).
            p = jax.device_put(p, jax.tree.map(
                lambda _: NamedSharding(mesh, P()), p))
        return p

    def next_batch(step):
        if mcfg.task == "cls":
            pts, labels = synthetic.classification_batch(
                cfg.seed + 11, step, cfg.batch, cfg.n_points)
        else:
            pts, labels = synthetic.segmentation_batch(
                cfg.seed + 11, step, cfg.batch, cfg.n_points)
        batch = {"points": pts, "labels": labels}
        if mesh is None:
            return batch
        split = pnn.batch_spec(mcfg, mesh, cfg.batch)
        if split is not None:
            return jax.device_put(batch, NamedSharding(mesh, split))
        with logical.logical_rules(mesh, rules):
            sh = {"points": NamedSharding(
                      mesh, logical.spec(("batch", "points", None))),
                  "labels": NamedSharding(
                      mesh, logical.spec(("batch",) + (("points",)
                                         if mcfg.task == "seg" else ())))}
        return jax.device_put(batch, logical.fit_specs(sh, batch, mesh))

    opt_cfg = opt_lib.OptConfig(lr=cfg.lr, warmup=0,
                                total_steps=max(cfg.steps, 1),
                                weight_decay=cfg.weight_decay)
    base = make_train_step(mcfg, opt_cfg, mesh)

    def train_step(params, opt_state, batch, return_grads=False):
        if mesh is None:
            return base(params, opt_state, batch, return_grads)
        with logical.logical_rules(mesh, rules):
            return base(params, opt_state, batch, return_grads)

    loop_cfg = loop_lib.LoopConfig(
        total_steps=cfg.steps, ckpt_dir=cfg.ckpt_dir,
        ckpt_every=cfg.ckpt_every, log_every=max(1, cfg.steps // 5),
        grad_compression=cfg.grad_compression, seed=cfg.seed)
    return loop_lib.run(loop_cfg, init_params=init_params,
                        train_step=train_step, next_batch=next_batch,
                        opt_cfg=opt_cfg, params=params, log=log)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="pointnet2_cls",
                    choices=sorted(PRESETS))
    ap.add_argument("--n", type=int, default=192)
    ap.add_argument("--th", type=int, default=32)
    ap.add_argument("--point-ops", default="bppo",
                    choices=["bppo", "global"])
    ap.add_argument("--impl", default=None, choices=["xla", "pallas"],
                    help="point-op execute backend (default: "
                         "$REPRO_POINT_IMPL or xla) — both differentiate")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default="none", choices=["none", "auto"])
    ap.add_argument("--leaf-chunk", type=int, default=None)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--compression", default="none",
                    choices=["none", "bf16", "int8"])
    args = ap.parse_args(argv)
    compile_cache.enable()

    cfg = TrainConfig(preset=args.preset, n_points=args.n, th=args.th,
                      point_ops=args.point_ops, impl=args.impl,
                      batch=args.batch, steps=args.steps, lr=args.lr,
                      seed=args.seed, mesh=args.mesh,
                      leaf_chunk=args.leaf_chunk, ckpt_dir=args.ckpt,
                      grad_compression=args.compression)
    _, _, info = fit(cfg)
    h = info["history"]
    if h:
        print(f"[train.pnn] done: loss {h[0]['loss']:.4f} -> "
              f"{h[-1]['loss']:.4f} over {len(h)} steps; "
              f"{info['monitor']}")
    else:
        print("[train.pnn] nothing to do: checkpoint already at "
              f"step >= {args.steps}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
