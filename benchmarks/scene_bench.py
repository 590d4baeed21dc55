"""Scene-scale inference trajectory (docs/DESIGN.md §10): points/s and
peak-memory scaling for ``repro.scene`` across scene sizes.

This is the workload the paper's "large-scale" claim is about: a single
100k–1M-point cloud segmented end to end without ever materializing an
O(n²) point op — the scene is tiled into fixed-shape blocks, tiles stream
through the bucketed serving engine (one executable per bucket, compiled
in ``warm()`` and excluded from the timings), and logits stitch back by
owner tile; wall-clock covers tiling + dispatch + stitch.

Every (impl, n) runs in this one process, which holds the device: a child
started after JAX has touched a TPU could not reach it.  Sizes run in
ascending order, so the device's peak-bytes watermark read after each size
(``device.memory_stats()``, reported where the backend has it — the TPU
does, the CPU does not) is that size's peak.

Rows (see benchmarks/README.md):
  scene/<impl>/n<k>/infer       end-to-end µs; derived points_per_s, tiles,
                                halo_points, peak_device_mb
  scene/<impl>/n<k>/compile     warm() compile seconds (excluded above)

CLI (the CI scene-smoke leg):
  PYTHONPATH=src python -m benchmarks.scene_bench --n 16384 --json bench_out
"""
from __future__ import annotations

import argparse
import time

import jax

from benchmarks.common import emit
from repro import scene
from repro.data import synthetic
from repro.kernels import ops as kops


def _peak_device_mb() -> str:
    """The device's peak bytes in use so far, or "not_measured" where the
    backend keeps no memory statistics (the CPU)."""
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not_measured" if peak is None else f"{peak / 2**20:.0f}"


def _one_scene(impl, n, tile_points, th, halo, microbatch, mesh):
    """One (impl, n) measurement."""
    coords, _ = synthetic.scene(0, n)
    cfg = scene.SceneConfig(tile_points=tile_points, halo=halo, th=th,
                            impl=impl, microbatch=microbatch, mesh=mesh)
    eng = scene.SceneEngine(cfg)
    t0 = time.monotonic()
    eng.warm()
    compile_s = time.monotonic() - t0
    t0 = time.monotonic()
    logits, plan = eng.infer(coords)
    dt = time.monotonic() - t0
    assert logits.shape == (n, cfg.num_classes)
    return {"dt": dt, "compile_s": compile_s, "tiles": plan.num_tiles,
            "halo_points": plan.halo_points, "max_tile": plan.max_tile_n,
            "peak_device_mb": _peak_device_mb()}


def run(quick: bool = True, impl: str | None = None, *,
        ns: tuple | None = None, tile_points: int = 4096, th: int = 256,
        halo: float = 0.1, microbatch: int = 4, mesh: str = "none"):
    impls = ([kops.resolve_impl(impl)] if impl is not None
             else ["xla", "pallas"])
    ns = sorted(ns or ((16_384,) if quick else (16_384, 65_536, 262_144)))
    note = "" if jax.default_backend() == "tpu" else "interpret_mode"
    for im in impls:
        for n in ns:
            m = _one_scene(im, n, tile_points, th, halo, microbatch, mesh)
            emit(f"scene/{im}/n{n}/compile", m["compile_s"] * 1e6,
                 "excluded_from_infer")
            emit(f"scene/{im}/n{n}/infer", m["dt"] * 1e6,
                 f"points_per_s={n / m['dt']:.4g};tiles={m['tiles']};"
                 f"halo_points={m['halo_points']};"
                 f"max_tile={m['max_tile']};"
                 f"peak_device_mb={m['peak_device_mb']}"
                 + (f";{note}" if note and im == "pallas" else ""))
    return ",".join(impls)  # backend(s) that ran, for the JSON meta


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", default="16384",
                    help="comma-separated scene sizes")
    ap.add_argument("--tile-points", type=int, default=4096)
    ap.add_argument("--th", type=int, default=256)
    ap.add_argument("--halo", type=float, default=0.1)
    ap.add_argument("--microbatch", type=int, default=4)
    ap.add_argument("--impl", default=None, choices=["xla", "pallas"],
                    help="default: both backends")
    ap.add_argument("--mesh", default="none", choices=["none", "auto"],
                    help="auto: shard tile microbatches over the elastic "
                         "host mesh")
    ap.add_argument("--json", default=None, metavar="DIR",
                    help="write BENCH_scene.json into DIR")
    args = ap.parse_args(argv)

    import sys

    from benchmarks import common
    from benchmarks.run import _write_suite_json

    ns = tuple(int(x) for x in args.n.split(","))
    quick = max(ns) < 262_144      # paper-scale runs are not CI smoke
    print("name,us_per_call,derived")
    t0 = time.time()
    ran = run(quick=quick, impl=args.impl, ns=ns,
              tile_points=args.tile_points, th=args.th, halo=args.halo,
              microbatch=args.microbatch, mesh=args.mesh)
    if args.json:
        path = _write_suite_json(args.json, "scene", common.ROWS,
                                 {"quick": quick, "impl": ran,
                                  "elapsed_s": round(time.time() - t0, 3),
                                  "unix_time": int(t0)})
        print(f"# wrote {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
