"""PNN dry-run cells — the paper's own workloads on the production mesh.

By default the cell lowers a *serving* step (the paper is an inference
accelerator): Fractal partition -> BPPO point ops -> PNN feature stages,
for PointNeXt segmentation at S3DIS scale (33K / 289K points, paper
Figs. 13/15/18).  With ``kind="train"`` it lowers the *fine-tune* step
instead — ``jax.value_and_grad`` through the same pipeline plus the AdamW
update (the execute-phase VJPs of kernels/vjp.py make this valid for
either impl) — proving the backward pass compiles at production scale.
Sharding: clouds -> ``data``, fractal leaves -> ``model`` (the paper's
inter-block parallelism promoted to chips; docs/DESIGN.md §6).

Called from dryrun.py via ``--arch pointnext --shape pnn_289k``
(``--train`` for the train cell).
"""
from __future__ import annotations

import dataclasses
import math
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist import logical
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.models import pnn
from repro.train import optimizer as opt_lib


@dataclasses.dataclass(frozen=True)
class PNNShape:
    name: str
    n_points: int
    batch: int
    th: int


PNN_SHAPES = {
    "pnn_33k": PNNShape("pnn_33k", 33_000, 16, 256),
    "pnn_289k": PNNShape("pnn_289k", 289_000, 16, 256),
    "pnn_1m": PNNShape("pnn_1m", 1_000_000, 4, 256),
}

PNN_VARIANTS = {
    "pointnet2": pnn.pointnet2_seg,
    "pointnext": pnn.pointnext_seg,
    "pointvector": pnn.pointvector_seg,
}


def _model_flops(cfg: pnn.PNNConfig, n: int, batch: int, params) -> float:
    """Useful FLOPs: MLP matmuls over grouped features + point-op distance
    updates (3 mul + 3 add per pair)."""
    total = 0.0
    sizes = cfg.stage_sizes()
    c_in = cfg.in_channels
    for i, s in enumerate(cfg.stages):
        m = sizes[i + 1]
        widths = (c_in + 3,) + tuple(s.widths)
        for a, b in zip(widths[:-1], widths[1:]):
            total += 2.0 * m * s.nsample * a * b
        # FPS within blocks: k iterations x block size; BQ: centers x window
        total += 6.0 * sizes[i] * (s.rate * cfg.th) + \
            6.0 * m * s.nsample * 2 * cfg.th
        c_in = s.widths[-1]
    for widths in cfg.fp_widths:
        m = sizes[-1]
        for a, b in zip((c_in,) + tuple(widths)[:-1], widths):
            total += 2.0 * m * a * b
    return total * batch


def run_pnn_cell(variant: str, shape_name: str, *, multi_pod: bool = False,
                 verbose: bool = True, rules=None, leaf_chunk: int = 512,
                 point_ops: str = "bppo", impl: str | None = None,
                 batch: int | None = None, kind: str = "serve"):
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    chips = mesh.devices.size
    shape = PNN_SHAPES[shape_name]
    if batch is not None:
        shape = dataclasses.replace(shape, batch=batch)
    cfg = PNN_VARIANTS[variant](n=shape.n_points, point_ops=point_ops,
                                th=shape.th, impl=impl)
    cfg = dataclasses.replace(cfg, leaf_chunk=leaf_chunk)

    t0 = time.monotonic()
    params = jax.eval_shape(
        lambda: pnn.init(jax.random.PRNGKey(0), cfg))
    clouds = jax.ShapeDtypeStruct((shape.batch, shape.n_points, 3),
                                  jnp.float32)

    def serve_step(params, clouds):
        return pnn.apply_batch(params, cfg, clouds, mesh=mesh)

    rules = rules or logical.RULES_V0
    batch_axes = rules.get("batch", ("pod", "data"))
    batch_axes = tuple(a for a in (batch_axes or ())
                       if a in mesh.axis_names)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    # jit argument shardings must divide evenly: drop axes until they do.
    while batch_axes and shape.batch % \
            math.prod(sizes[a] for a in batch_axes):
        batch_axes = batch_axes[1:]
    cloud_sh = NamedSharding(
        mesh, P(batch_axes) if batch_axes else P())
    with logical.logical_rules(mesh, rules):
        if kind == "train":
            from repro.train.pnn import train_step_fn
            labels = jax.ShapeDtypeStruct(
                (shape.batch,) + ((shape.n_points,)
                                  if cfg.task == "seg" else ()), jnp.int32)
            opt_shapes = jax.eval_shape(opt_lib.init, params)
            label_sh = NamedSharding(
                mesh, P(batch_axes) if batch_axes else P())
            # The exact step the trainer runs (train/pnn.py), lowered with
            # the cell's shardings instead of its jit.
            train_step = train_step_fn(cfg, opt_lib.OptConfig(warmup=0),
                                       mesh)
            b_sh = {"points": cloud_sh, "labels": label_sh}
            lowered = jax.jit(
                train_step, in_shardings=(None, None, b_sh),
                out_shardings=(None, None, None)).lower(
                    params, opt_shapes, {"points": clouds,
                                         "labels": labels})
        else:
            lowered = jax.jit(serve_step, in_shardings=(None, cloud_sh),
                              out_shardings=cloud_sh).lower(params, clouds)
        compiled = lowered.compile()

    model_flops = _model_flops(cfg, shape.n_points, shape.batch, params)
    if kind == "train":
        model_flops *= 3.0  # fwd + bwd, the usual 1:2 convention
    row = rl.analyze(compiled, arch=variant,
                     shape=f"{shape_name}_train" if kind == "train"
                     else shape_name,
                     mesh_name=mesh_name, chips=chips,
                     model_flops=model_flops)
    d = row.to_dict()
    d["compile_s"] = time.monotonic() - t0
    d["kind"] = kind
    if verbose:
        mem = d["mem_per_device"]
        print(f"[dryrun:pnn] {variant} x {shape_name} on {mesh_name}: "
              f"peak {mem['peak_mb']/1024:.2f} GB/device | "
              f"flops/chip {d['hlo_flops_per_chip']:.3e} | "
              f"coll {d['coll_bytes_per_chip']/2**20:.1f} MB | "
              f"bound={d['bottleneck']} | compile {d['compile_s']:.0f}s",
              flush=True)
    return d
