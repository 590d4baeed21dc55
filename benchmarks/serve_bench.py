"""Serving perf trajectory (docs/DESIGN.md §9): per-bucket latency
percentiles and sustained throughput for a mixed-size request stream
through ``repro.serve``.

Each impl serves the same stream: clouds padded to their minimal bucket,
fixed microbatches, plan cache warmed *before* the stream so latencies
exclude compile (compile time gets its own row).  With no ``--impl`` both
backends run, so one ``BENCH_serve.json`` carries the xla and pallas
trajectories side by side (on the CPU pallas runs in interpret mode —
correctness path, wall-clock not meaningful).

Rows (see benchmarks/README.md for the schema):
  serve/<impl>/bucket<n>/p50|p95|p99   latency percentiles (us_per_call)
  serve/<impl>/bucket<n>/throughput    derived clouds_per_s
  serve/<impl>/compile/n<n>            warmup compile (excluded above)
  serve/<impl>/stream                  whole-stream throughput + cache

CLI (the CI smoke leg):
  PYTHONPATH=src python -m benchmarks.serve_bench --requests 8 --n 4096 \
      --json bench_out
"""
from __future__ import annotations

import argparse

import jax

from benchmarks.common import emit
from repro.kernels import ops as kops


def _serve_stream(impl, *, buckets, requests, microbatch, th, mesh):
    from repro import serve
    from repro.data import synthetic

    # Generous deadline: batches dispatch when *full* (the packed-path
    # numbers this suite tracks); the tail flushes partial at stream end.
    cfg = serve.ServeConfig(buckets=buckets, microbatch=microbatch,
                            max_wait_s=60.0, th=th, impl=impl, mesh=mesh)
    engine = serve.ServeEngine(cfg)
    compile_s = engine.warm()
    for r, n in enumerate(serve.mixed_request_sizes(buckets, requests)):
        clouds, _ = synthetic.segmentation_batch(0, r, 1, n)
        engine.submit(clouds[0])
        for done in engine.step():
            engine.take(done)
    for done in engine.flush():
        engine.take(done)
    return engine.stats(), compile_s


def run(quick: bool = True, impl: str | None = None, *,
        requests: int | None = None, buckets: tuple | None = None,
        microbatch: int | None = None, th: int = 256, mesh: str = "none"):
    impls = ([kops.resolve_impl(impl)] if impl is not None
             else ["xla", "pallas"])
    buckets = buckets or ((1024, 4096) if quick else (4096, 16384, 65536))
    requests = requests or (8 if quick else 32)
    microbatch = microbatch or (2 if quick else 4)
    note = "" if jax.default_backend() == "tpu" else "interpret_mode"
    for im in impls:
        st, compile_s = _serve_stream(im, buckets=buckets,
                                      requests=requests,
                                      microbatch=microbatch, th=th,
                                      mesh=mesh)
        for b, s in compile_s.items():
            emit(f"serve/{im}/compile/n{b}", s * 1e6,
                 "excluded_from_latency")
        for b, row in sorted(st["buckets"].items()):
            for pct in ("p50", "p95", "p99"):
                emit(f"serve/{im}/bucket{b}/{pct}", row[f"{pct}_ms"] * 1e3,
                     f"count={row['count']}"
                     + (f";{note}" if note and im == "pallas" else ""))
            if row["clouds_per_s"] is not None:
                emit(f"serve/{im}/bucket{b}/throughput", 0.0,
                     f"clouds_per_s={row['clouds_per_s']:.4g}")
        pc = st["plan_cache"]
        one_trace = all(v == 1 for v in pc["traces"].values())
        if st["clouds_per_s"] is None:
            # No microbatch completed: stats() reports None rather than a
            # clamp-divided absurdity — nothing to emit for throughput.
            emit(f"serve/{im}/stream", 0.0,
                 f"clouds_per_s=none;executables={pc['executables']};"
                 f"one_trace_per_key={one_trace}")
        else:
            emit(f"serve/{im}/stream", 0.0,
                 f"clouds_per_s={st['clouds_per_s']:.4g};"
                 f"mpts_per_s={st['mpts_per_s']:.4g};"
                 f"executables={pc['executables']};"
                 f"one_trace_per_key={one_trace}")
    return ",".join(impls)  # backend(s) that ran, for the JSON meta


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--n", type=int, default=4096,
                    help="largest bucket; the ladder is (n//4, n)")
    ap.add_argument("--buckets", default=None,
                    help="explicit comma-separated ladder (overrides --n)")
    ap.add_argument("--microbatch", type=int, default=2)
    ap.add_argument("--th", type=int, default=256)
    ap.add_argument("--impl", default=None, choices=["xla", "pallas"],
                    help="default: both backends")
    ap.add_argument("--mesh", default="none", choices=["none", "auto"],
                    help="auto: shard microbatches over the elastic host "
                         "mesh (XLA logs involuntary-remat warnings for "
                         "the gather-heavy point ops on CPU)")
    ap.add_argument("--json", default=None, metavar="DIR",
                    help="write BENCH_serve.json into DIR")
    args = ap.parse_args(argv)

    buckets = (tuple(int(b) for b in args.buckets.split(","))
               if args.buckets else (max(1, args.n // 4), args.n))
    from benchmarks import common
    from benchmarks.run import _write_suite_json
    import sys
    import time

    quick = max(buckets) < 65_536  # paper-scale ladders are not CI smoke
    print("name,us_per_call,derived")
    t0 = time.time()
    ran = run(quick=quick, impl=args.impl, requests=args.requests,
              buckets=buckets, microbatch=args.microbatch, th=args.th,
              mesh=args.mesh)
    if args.json:
        path = _write_suite_json(args.json, "serve", common.ROWS,
                                 {"quick": quick, "impl": ran,
                                  "elapsed_s": round(time.time() - t0, 3),
                                  "unix_time": int(t0)})
        print(f"# wrote {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
