"""Find the serving knee once: offer the serve cell's stream at a series of
fixed rates, in one process with one set-up, and report for each rate the
clouds answered per second, the latency tail, and whether the backlog
grew (the second half of the window waits much longer than the first, or
the queue takes long to drain after the last arrival).

    python -m bench.tools.sweep --workload s3dis_serve_overload \
        --rates 60,80,100,120,140 --seconds 10 --seed 1

The knee is the highest rate with no growing backlog; a serve traffic
file holds its rate as a number.  Results go to
``<out>/sweep.json`` (``--out``, default ``bench/.results``) as well as
to standard output.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

from bench import arrivals, clouds, device
from bench import run as brun
from bench.drivers import serve


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="s3dis_serve_overload")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=str(brun.ROOT / "bench" / ".results"))
    args = ap.parse_args(argv)
    bench = json.loads((brun.ROOT / "BENCHMARK.json").read_text())
    cell = brun.cell_of(bench, args.workload)
    sys.path.insert(0, str(brun.ROOT / "src"))
    from bench import model
    chip = device.require_tpu(cell["chips"])
    brun.use_compile_cache()
    traffic = json.loads((brun.ROOT / "bench" / "traffic" /
                          f"{cell['traffic']}.json").read_text())
    r = brun.Run(cell, model.load(cell["config"]), traffic, args.seed,
                 args.seconds, False, chip, device.CompileCounter())
    _, pts, eng = serve.build(r)
    rows = []
    for rate in [float(x) for x in args.rates.split(",")]:
        due = arrivals.poisson_due(rate, args.seconds, args.seed,
                                   traffic["arrivals"]["order_seed"])
        pick = clouds.rng_for(args.seed, 102).integers(0, len(pts), len(due))
        t0, done_at, late, _, _ = serve.window(
            eng, pts, due, pick, set(), traffic["serve"]["max_wait_s"],
            args.seconds)
        lat = arrivals.latencies(due, t0, done_at)
        half = due < args.seconds / 2
        row = {"rate_per_s": rate, "requests": len(due),
               "answered_per_s": float(np.isfinite(done_at).sum() /
                                       (np.nanmax(done_at) - t0)),
               "p50_ms": float(np.nanpercentile(lat, 50) * 1e3),
               "p95_ms": float(np.nanpercentile(lat, 95) * 1e3),
               "p95_first_half_ms": float(np.nanpercentile(lat[half], 95)
                                          * 1e3),
               "p95_second_half_ms": float(np.nanpercentile(lat[~half], 95)
                                           * 1e3),
               "drain_s": float(np.nanmax(done_at) - t0 - args.seconds),
               "late_max_ms": float(late.max() * 1e3)}
        rows.append(row)
        print(json.dumps(row), flush=True)
        if row["drain_s"] > 2.0:
            break           # past the knee: higher rates only queue more
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    (out / "sweep.json").write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
