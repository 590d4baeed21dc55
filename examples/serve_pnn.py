"""Batched point-cloud segmentation serving — thin client of ``repro.serve``.

A mixed-size request stream flows through the serving subsystem
(docs/DESIGN.md §9): each cloud is padded to its minimal shape bucket, a
per-bucket queue packs fixed microbatches under a max-wait deadline, and a
plan cache keeps exactly one fractal-partition plan per (bucket, th,
strategy) and one compiled forward per (bucket, impl).  Compile happens in
``warm()`` — *before* the stream — so reported latencies never include it.

Run:  PYTHONPATH=src python examples/serve_pnn.py \
          [--buckets 1024,4096] [--requests 16] [--impl pallas] [--mesh auto]
"""
import argparse

from repro import compile_cache, serve
from repro.data import synthetic


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--buckets", default="1024,4096",
                    help="comma-separated shape-bucket ladder")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--microbatch", type=int, default=2)
    ap.add_argument("--max-wait-ms", type=float, default=20.0)
    ap.add_argument("--th", type=int, default=256)
    ap.add_argument("--variant", default="pointnext",
                    choices=["pointnet2", "pointnext", "pointvector"])
    ap.add_argument("--point-ops", default="bppo",
                    choices=["bppo", "global"])
    ap.add_argument("--impl", default=None, choices=["xla", "pallas"],
                    help="bppo execute backend (default: $REPRO_POINT_IMPL"
                         " or xla)")
    ap.add_argument("--mesh", default="none", choices=["none", "auto"],
                    help="auto: shard microbatches over the elastic host "
                         "mesh (repro.dist)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    compile_cache.enable()

    buckets = tuple(int(b) for b in args.buckets.split(","))
    cfg = serve.ServeConfig(
        buckets=buckets, microbatch=args.microbatch,
        max_wait_s=args.max_wait_ms / 1e3, variant=args.variant,
        th=args.th, point_ops=args.point_ops, impl=args.impl,
        mesh=args.mesh)
    engine = serve.ServeEngine(cfg, seed=args.seed)

    compile_s = engine.warm()
    print(f"warmed {len(compile_s)} buckets "
          f"({args.point_ops} point ops, impl={engine.impl}, th={args.th}, "
          f"mesh={args.mesh}): "
          + ", ".join(f"n={b} in {s:.1f}s" for b, s in compile_s.items())
          + "  [excluded from latencies]")

    sizes = serve.mixed_request_sizes(buckets, args.requests, args.seed)
    expect = {}
    for r, n in enumerate(sizes):
        clouds, _ = synthetic.segmentation_batch(args.seed, r, 1, n)
        rid = engine.submit(clouds[0])
        expect[rid] = n
        for done in engine.step():
            # pop-on-read; sanity: per-point logits for the real points
            assert engine.take(done).shape == (expect.pop(done),
                                               cfg.num_classes)
    for done in engine.flush():
        assert engine.take(done).shape == (expect.pop(done),
                                           cfg.num_classes)

    st = engine.stats()
    if st["clouds_per_s"] is None:   # no microbatch completed
        print(f"served {st['served']} clouds (no completed window)")
    else:
        print(f"served {st['served']} clouds in {st['wall_s']:.2f}s: "
              f"{st['clouds_per_s']:.2f} clouds/s "
              f"({st['mpts_per_s']:.3g} Mpts/s)")
    for b, row in sorted(st["buckets"].items()):
        print(f"  bucket n={b}: {row['count']} clouds, "
              f"p50 {row['p50_ms']:.1f} / p95 {row['p95_ms']:.1f} / "
              f"p99 {row['p99_ms']:.1f} ms")
    pc = st["plan_cache"]
    print(f"plan cache: {pc['executables']} executables, "
          f"{pc['hits']} hits, {pc['misses']} misses "
          f"(one trace per key: "
          f"{all(v == 1 for v in pc['traces'].values())})")


if __name__ == "__main__":
    main()
