"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this jits the real step function (train_step / prefill /
decode) with in/out shardings derived from the logical-axes trees, compiles
it for the production mesh built from 512 placeholder host devices, prints
``memory_analysis()`` (fits/doesn't) and ``cost_analysis()`` (FLOPs/bytes),
parses the collective schedule, and emits a roofline JSON row.

Usage:
  python -m repro.launch.dryrun --arch gemma3-12b --shape train_4k
  python -m repro.launch.dryrun --all --multi-pod --out results.json
  python -m repro.launch.dryrun --arch pointnext --shape pnn_289k  # PNN cell
  python -m repro.launch.dryrun --arch pointnext --shape pnn_33k --train

The 512 placeholder devices are requested in ``main`` (``force_host_devices``),
never on import, so importing this module leaves ``XLA_FLAGS`` alone.
"""
import argparse
import json
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.configs.shapes import SHAPES, applicable
from repro.dist import logical
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.lm import model as M
from repro.lm import steps as steps_lib
from repro.train import optimizer as opt_lib

BATCH_AXES = {
    "tokens": ("batch", None), "labels": ("batch", None),
    "dec_tokens": ("batch", None), "loss_mask": ("batch", None),
    "frames": ("batch", None, "embed"),
}


def _shardings_for_axes(axes_tree, mesh, rules=None):
    return logical.param_specs(axes_tree, mesh, rules)


# Shared spec-fitting lives in dist.logical; keep the local names this
# module's call sites were built against.
_axis_size = logical.entry_size
_fit_shardings = logical.fit_specs


def _rules_for(shape, mesh):
    """Cell-adapted rules: small-batch decode drops batch sharding and
    spreads the KV/cache sequence over both axes instead."""
    rules = dict(logical.RULES_V0)
    dp = _axis_size(mesh, tuple(a for a in ("pod", "data")
                                if a in mesh.axis_names))
    if shape.kind == "decode" and shape.global_batch % dp:
        rules["batch"] = None
        rules["kv_seq"] = ("pod", "data", "model")
    return rules


def _batch_shardings(specs, mesh, rules):
    ctx = logical._Ctx(mesh, rules)

    def one(path, leaf):
        name = str(path[-1].key)
        ax = BATCH_AXES[name]
        return NamedSharding(
            mesh, P(*[logical._axis_to_mesh(ctx, a) for a in ax]))

    flat = jax.tree_util.tree_flatten_with_path(specs)
    leaves = [one(p, l) for p, l in flat[0]]
    return jax.tree_util.tree_unflatten(flat[1], leaves)


# Gradient-accumulation policy for train cells: activation memory control
# at fixed global batch (metric compiles use 1; total FLOPs are unchanged).
MICROBATCH = {"llama4-scout-17b-16e": 4, "chameleon-34b": 4,
              "zamba2-7b": 4, "gemma3-12b": 4, "minitron-4b": 2,
              "gemma2-2b": 2, "granite-moe-3b-a800m": 8, "xlstm-1.3b": 4}


def _compile_cell(cfg, shape, mesh, rules, opt_overrides=None,
                  microbatch=1):
    """Lower + compile one step function; returns the compiled object."""
    param_shapes, axes = steps_lib.eval_shape_init(cfg)
    p_sh = _fit_shardings(_shardings_for_axes(axes, mesh, rules),
                          param_shapes, mesh)
    with logical.logical_rules(mesh, rules):
        if shape.kind == "train":
            opt_cfg = opt_lib.OptConfig(**(opt_overrides or {}))
            step = steps_lib.make_train_step(cfg, opt_cfg,
                                             microbatch=microbatch)
            batch_specs = steps_lib.batch_specs(cfg, shape)
            opt_shapes = jax.eval_shape(opt_lib.init, param_shapes)
            o_sh = _fit_shardings(
                logical.param_specs(opt_lib.init_axes(axes), mesh, rules),
                opt_shapes, mesh)
            b_sh = _fit_shardings(_batch_shardings(batch_specs, mesh, rules),
                                  batch_specs, mesh)
            jitted = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                             out_shardings=(p_sh, o_sh, None))
            lowered = jitted.lower(param_shapes, opt_shapes, batch_specs)
        elif shape.kind == "prefill":
            step = steps_lib.make_prefill_step(cfg, max_len=shape.seq_len)
            batch_specs = steps_lib.prefill_specs(cfg, shape)
            b_sh = _fit_shardings(_batch_shardings(batch_specs, mesh, rules),
                                  batch_specs, mesh)
            cache_shapes = jax.eval_shape(
                lambda: M.init_cache(None, cfg, shape.global_batch,
                                     shape.seq_len,
                                     enc_len=shape.seq_len
                                     if cfg.encoder_layers else None))
            c_sh = _fit_shardings(
                logical.param_specs(_stacked_cache_axes(cfg), mesh, rules),
                cache_shapes, mesh)
            jitted = jax.jit(step, in_shardings=(p_sh, b_sh),
                             out_shardings=(None, c_sh))
            lowered = jitted.lower(param_shapes, batch_specs)
        else:  # decode
            step = steps_lib.make_decode_step(cfg)
            token, cache_specs, pos = steps_lib.decode_specs(cfg, shape)
            c_sh = _fit_shardings(
                logical.param_specs(_stacked_cache_axes(cfg), mesh, rules),
                cache_specs, mesh)
            t_spec = P(tuple(a for a in ("pod", "data")
                             if a in mesh.axis_names))
            t_sh = _fit_shardings(NamedSharding(mesh, t_spec), token, mesh)
            jitted = jax.jit(step, in_shardings=(p_sh, t_sh, c_sh, None),
                             out_shardings=(None, c_sh))
            lowered = jitted.lower(param_shapes, token, cache_specs, pos)
        return lowered.compile()


def _metric_cfg(cfg, shape, reps: int):
    """Unrolled small-depth variant for cost measurement.

    XLA's cost analysis counts while-loop bodies once, so metric compiles
    unroll the layer stack (and inner chunk scans / the chunked loss) and
    the full-depth costs are fitted linearly from 1-rep and 2-rep runs."""
    import dataclasses as dc
    kw = dict(n_layers=reps * len(cfg.pattern), scan_layers=False,
              loss_chunk=shape.seq_len, unroll_inner=True)
    # mLSTM/sLSTM inner scans are NOT unrolled (32k/64 = 512 body copies
    # explode compile time); their in-scan flops are added analytically.
    return dc.replace(cfg, **kw)


def _metrics_of(compiled):
    ca = compiled.cost_analysis() or {}
    by, cnt = rl.parse_collectives(compiled.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": sum(by.values()), "by_kind": by, "cnt": cnt}


def _fit(m1, m2, reps):
    """metric(reps) = outside + body*reps, from 1-rep/2-rep measurements."""
    body = {k: max(m2[k] - m1[k], 0.0) for k in ("flops", "bytes", "coll")}
    out = {k: max(m1[k] - body[k], 0.0) for k in body}
    fitted = {k: out[k] + body[k] * reps for k in body}
    kinds = set(m1["by_kind"]) | set(m2["by_kind"])
    fitted["by_kind"] = {}
    for k in kinds:
        a, b2 = m1["by_kind"].get(k, 0.0), m2["by_kind"].get(k, 0.0)
        body_k = max(b2 - a, 0.0)
        fitted["by_kind"][k] = max(a - body_k, 0.0) + body_k * reps
    fitted["cnt"] = {k: m2["cnt"].get(k, 0) for k in kinds}
    return fitted


def _xlstm_extra_flops(cfg, shape):
    """Analytic add-back for the xLSTM inner scans (not unrollable at
    metric-compile time): sLSTM recurrent R-matmuls and the mLSTM chunk
    body (intra-chunk qk/value products + state update/inter-chunk reads).
    Projections live outside the scans and are fitted empirically."""
    if cfg.xlstm is None or shape.kind == "decode":
        return 0.0
    nh = cfg.xlstm.n_heads
    tokens = shape.global_batch * shape.seq_len
    mult = 3.0 if shape.kind == "train" else 1.0
    total = 0.0
    n_slstm = sum(1 for k in cfg.pattern if k == "slstm") * cfg.reps
    if n_slstm:
        hd = cfg.d_model // nh
        total += 2.0 * nh * hd * 4 * hd * tokens * n_slstm * mult
    n_mlstm = sum(1 for k in cfg.pattern if k == "mlstm") * cfg.reps
    if n_mlstm:
        di = cfg.xlstm.d_inner(cfg.d_model)
        hd = di // nh
        L = cfg.xlstm.chunk
        # per token: qk + y_num ~ 4*L*hd*nh ; state update + inter ~ 6*hd^2*nh
        per_tok = 4.0 * L * hd * nh + 6.0 * hd * hd * nh
        total += per_tok * tokens * n_mlstm * mult
    return total


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             rules=None, opt_overrides=None, verbose=True,
             cfg_overrides=None, metrics: bool = True,
             microbatch: int | None = None):
    """Dry-run one (arch x shape x mesh) cell.

    1. full-depth scan compile  -> proof-of-compile + memory_analysis
    2. unrolled 1-rep + 2-rep metric compiles -> fitted FLOPs/bytes/coll
       (``metrics=False`` skips #2 — multi-pod sweep: compile proof +
       memory + collective schedule only; roofline terms come from the
       single-pod table)
    """
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    chips = mesh.devices.size
    shape = SHAPES[shape_name]
    rules = rules or _rules_for(shape, mesh)
    cfg = configs.lm_config(arch, **(cfg_overrides or {}))
    ok, why = applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "skipped": why}

    t0 = time.monotonic()
    param_shapes, _ = steps_lib.eval_shape_init(cfg)
    n_active = rl.active_params(cfg, param_shapes)
    n_total = rl.count_params(param_shapes)
    model_flops = rl.model_flops_for(cfg, shape.kind, shape.seq_len,
                                     shape.global_batch, n_active)

    full = _compile_cell(cfg, shape, mesh, rules, opt_overrides,
                         microbatch=microbatch if microbatch is not None
                         else MICROBATCH.get(arch, 1))
    t_full = time.monotonic() - t0
    if metrics:
        m1 = _metrics_of(_compile_cell(_metric_cfg(cfg, shape, 1), shape,
                                       mesh, rules, opt_overrides))
        m2 = _metrics_of(_compile_cell(_metric_cfg(cfg, shape, 2), shape,
                                       mesh, rules, opt_overrides))
        fitted = _fit(m1, m2, cfg.reps)
        fitted["flops"] += _xlstm_extra_flops(cfg, shape) / chips
    else:
        fitted = _metrics_of(full)  # raw: while bodies counted once

    ma = full.memory_analysis()
    mem = {"argument_mb": ma.argument_size_in_bytes / 2**20,
           "output_mb": ma.output_size_in_bytes / 2**20,
           "temp_mb": ma.temp_size_in_bytes / 2**20,
           "peak_mb": (ma.argument_size_in_bytes + ma.output_size_in_bytes
                       + ma.temp_size_in_bytes) / 2**20}
    row = rl.Roofline(arch=arch, shape=shape_name, mesh=mesh_name,
                      chips=chips, hlo_flops=fitted["flops"],
                      hlo_bytes=fitted["bytes"], coll_bytes=fitted["coll"],
                      coll_by_kind=fitted["by_kind"],
                      coll_count=fitted["cnt"], model_flops=model_flops,
                      mem_per_device=mem)
    d = row.to_dict()
    d.update({"compile_s": time.monotonic() - t0, "compile_full_s": t_full,
              "n_params": n_total, "n_active": n_active,
              "metrics_mode": "fitted" if metrics else "raw"})
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} on {mesh_name}: "
              f"peak {mem['peak_mb']/1024:.2f} GB/device | "
              f"flops/chip {d['hlo_flops_per_chip']:.3e} | "
              f"coll {d['coll_bytes_per_chip']/2**20:.1f} MB | "
              f"bound={d['bottleneck']} useful={d['usefulness']*100:.0f}% "
              f"| compile {d['compile_s']:.0f}s", flush=True)
    return d


def _stacked_cache_axes(cfg):
    return M.cache_axes(cfg)


def force_host_devices(n: int = 512):
    """Ask the CPU backend for ``n`` placeholder devices, keeping any flags
    already in ``XLA_FLAGS``.  Takes effect only before JAX first touches
    a device, so CLI ``main``s call it first thing."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}").strip()


def main(argv=None):
    force_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-metrics", action="store_true")
    ap.add_argument("--impl", default=None, choices=["xla", "pallas"],
                    help="point-op execute backend for the PNN cells")
    ap.add_argument("--train", action="store_true",
                    help="lower the PNN fine-tune step (value_and_grad + "
                         "AdamW) instead of the serving step — proves the "
                         "backward pass compiles at production scale")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from repro.launch.pnn_cell import PNN_SHAPES, PNN_VARIANTS, run_pnn_cell

    cells = []
    if args.all:
        for arch in configs.ARCHS:
            for shape in SHAPES:
                cells.append((arch, shape))
        for variant in PNN_VARIANTS:
            cells.append((variant, "pnn_289k"))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    rows, failures = [], []

    def flush():
        if args.out:
            with open(args.out, "w") as f:
                json.dump({"rows": rows, "failures": failures}, f, indent=1)

    for mp in meshes:
        for arch, shape in cells:
            try:
                if arch in PNN_VARIANTS:
                    rows.append(run_pnn_cell(
                        arch, shape, multi_pod=mp, impl=args.impl,
                        kind="train" if args.train else "serve"))
                else:
                    rows.append(run_cell(arch, shape, multi_pod=mp,
                                         metrics=not args.no_metrics))
            except Exception as e:  # noqa: BLE001 - report and continue
                traceback.print_exc()
                failures.append({"arch": arch, "shape": shape,
                                 "multi_pod": mp, "error": str(e)})
            flush()  # incremental: a timeout never loses completed cells
    real = [r for r in rows if "skipped" not in r]
    print(rl.format_table(real))
    for r in rows:
        if "skipped" in r:
            print(f"[skip] {r['arch']} x {r['shape']}: {r['skipped']}")
    if failures:
        print(f"FAILURES: {len(failures)}")
        for f_ in failures:
            print("  ", f_)
        sys.exit(1)


if __name__ == "__main__":
    main()
