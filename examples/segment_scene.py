"""Room-scale scene segmentation — thin client of ``repro.scene``.

One 16k–1M-point synthetic scene flows through the streaming scene path
(docs/DESIGN.md §10): a coarse fractal pre-partition cuts it into
DFT-contiguous tiles, each tile plus its halo ring is admitted to a shape
bucket and served by the plan-cached engine (one compile per bucket, done
in ``warm()``), and per-point logits stitch back under the owner-tile
rule.  No O(n²) op is ever materialized; peak memory is one microbatch of
tile tensors plus the (n, classes) output.

Run:  PYTHONPATH=src python examples/segment_scene.py \
          [--n 65536] [--tile-points 4096] [--halo 0.15] [--impl pallas]
"""
import argparse
import resource
import time

import numpy as np

from repro import compile_cache, scene
from repro.data import synthetic


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=65536)
    ap.add_argument("--tile-points", type=int, default=4096)
    ap.add_argument("--halo", type=float, default=0.15,
                    help="halo radius (0 disables border context)")
    ap.add_argument("--th", type=int, default=256)
    ap.add_argument("--microbatch", type=int, default=4)
    ap.add_argument("--variant", default="pointnet2",
                    choices=["pointnet2", "pointnext", "pointvector"])
    ap.add_argument("--impl", default=None, choices=["xla", "pallas"],
                    help="bppo execute backend (default: $REPRO_POINT_IMPL"
                         " or xla)")
    ap.add_argument("--mesh", default="none", choices=["none", "auto"],
                    help="auto: shard tile microbatches over the elastic "
                         "host mesh (repro.dist)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    compile_cache.enable()

    t0 = time.monotonic()
    coords, labels = synthetic.scene(args.seed, args.n)
    print(f"scene: {args.n} points, {len(np.unique(labels))} shape classes "
          f"({time.monotonic() - t0:.1f}s to generate)")

    cfg = scene.SceneConfig(
        tile_points=args.tile_points, halo=args.halo, th=args.th,
        microbatch=args.microbatch, variant=args.variant, impl=args.impl,
        mesh=args.mesh)
    eng = scene.SceneEngine(cfg, seed=args.seed)
    t0 = time.monotonic()
    compile_s = eng.warm()
    print(f"warmed {len(compile_s)} buckets (impl={eng.impl}, "
          f"th={args.th}, mesh={args.mesh}) in "
          f"{time.monotonic() - t0:.1f}s  [excluded from throughput]")

    t0 = time.monotonic()
    logits, plan = eng.infer(coords)
    dt = time.monotonic() - t0
    assert logits.shape == (args.n, cfg.num_classes)

    print(f"tiled: {plan.num_tiles} tiles (<= {args.tile_points} owned pts "
          f"each), {plan.halo_points} halo context points, "
          f"max tile cloud {plan.max_tile_n}")
    print(f"inferred: {args.n / dt:,.0f} points/s ({dt:.2f}s end to end, "
          f"tiling + dispatch + stitch)")
    pred = logits.argmax(-1)
    agree = (pred == labels).mean()
    counts = np.bincount(pred, minlength=cfg.num_classes)
    print(f"predictions (untrained params — structure demo, not accuracy): "
          f"class counts {counts.tolist()}, label agreement {agree:.3f}")
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024
    print(f"peak RSS {rss:.0f} MB "
          f"(~{1e6 * rss / args.n:.0f} bytes/point at this n)")


if __name__ == "__main__":
    main()
