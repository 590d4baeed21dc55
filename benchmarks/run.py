"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (plus a summary).  ``--full`` runs
paper-scale sizes (289K points, 400-step accuracy training); the default
quick mode keeps CI fast.  ``--impl xla|pallas`` selects the point-op
execute backend for the suites that dispatch kernels; ``--json DIR`` writes
one machine-readable ``BENCH_<suite>.json`` per suite so the perf
trajectory is tracked across PRs.

  partitioning   -> paper Figs. 5/16 (sorter vs traverser, 133x claim)
  point_ops      -> paper Figs. 4/13/15/18 (global vs BPPO, traffic model)
  threshold      -> paper Fig. 17 (th trade-off)
  accuracy       -> paper Fig. 14 (network accuracy, global vs BPPO)
  kernels        -> paper §VI-C RSPU ablation (reuse model + verification)
  serve          -> deployment path: bucketed serving latency/throughput
                    (docs/DESIGN.md §9; both impls unless --impl is given)
  train          -> fine-tune step time, fwd vs fwd+bwd through the
                    execute-phase VJPs (docs/DESIGN.md §4; both impls
                    unless --impl is given)
  scene          -> scene-scale streaming inference: points/s + peak-RSS
                    scaling over 16k-262k-point scenes (docs/DESIGN.md
                    §10; both impls unless --impl is given)

See benchmarks/README.md for the BENCH_<suite>.json schema.
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import subprocess
import sys
import time

from repro import compile_cache


def _git_sha() -> str:
    """The repo HEAD, so every BENCH_<suite>.json pins the code it
    measured (perf trajectories are diffed across PRs)."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10, cwd=os.path.dirname(os.path.abspath(__file__)))
        return out.stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def _write_suite_json(out_dir: str, suite: str, rows, meta: dict) -> str:
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"BENCH_{suite}.json")
    payload = dict(meta, suite=suite, git_sha=_git_sha(), rows=[
        {"name": n, "us_per_call": us, "derived": d} for n, us, d in rows])
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return path


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma list: partitioning,point_ops,threshold,"
                         "accuracy,kernels,serve,scene,train")
    ap.add_argument("--impl", default=None, choices=["xla", "pallas"],
                    help="point-op execute backend for kernel-dispatching "
                         "suites (default: $REPRO_POINT_IMPL or xla)")
    ap.add_argument("--json", default=None, metavar="DIR",
                    help="write one BENCH_<suite>.json per suite into DIR")
    args = ap.parse_args(argv)
    compile_cache.enable()
    quick = not args.full

    from benchmarks import (accuracy, common, kernels_bench, partitioning,
                            point_ops, scene_bench, serve_bench, threshold,
                            train_bench)
    suites = {
        "partitioning": partitioning.run,
        "point_ops": point_ops.run,
        "threshold": threshold.run,
        "accuracy": accuracy.run,
        "kernels": kernels_bench.run,
        "serve": serve_bench.run,
        "scene": scene_bench.run,
        "train": train_bench.run,
    }
    chosen = (args.only.split(",") if args.only else list(suites))
    print("name,us_per_call,derived")
    t0 = time.time()
    for name in chosen:
        fn = suites[name]
        kwargs = {"quick": quick}
        if args.impl and "impl" in inspect.signature(fn).parameters:
            kwargs["impl"] = args.impl
        row_start = len(common.ROWS)
        t_suite = time.time()
        ret = fn(**kwargs)
        if args.json:
            meta = {"quick": quick,
                    "elapsed_s": round(time.time() - t_suite, 3),
                    "unix_time": int(t_suite)}
            if isinstance(ret, str):
                # kernel-dispatching suites return the backend that ran
                # (--impl / $REPRO_POINT_IMPL resolved); others omit it.
                meta["impl"] = ret
            path = _write_suite_json(args.json, name,
                                     common.ROWS[row_start:], meta)
            print(f"# wrote {path}", file=sys.stderr)
    print(f"# total {time.time() - t0:.1f}s, quick={quick}",
          file=sys.stderr)


if __name__ == "__main__":
    main()
