"""Record the trace of one serve microbatch of a cell as a test fixture:
the serve driver's set-up (weights, cloud pool, warmed engine), then one
microbatch's clouds admitted under the harness span ``bench.admit`` and
served under ``serve.step`` while the profiler runs, as in the driver's
window.  The trace holds the device's operations and the harness's and the
program's host spans on one clock.

    python -m bench.tools.fixture --workload s3dis_serve_overload \
        --seed 7 --out chiprun_out/serve_spans.xplane.pb.gz

Writes the trace gzipped to ``--out`` and prints one JSON line: its size,
the longest idle gaps by span, and the per-layer metrics the cell reads
from the program's spans.
"""
from __future__ import annotations

import argparse
import gzip
import importlib
import json
import pathlib
import sys
import types

from bench import device, model, trace
from bench import run as brun


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    bench = json.loads((brun.ROOT / "BENCHMARK.json").read_text())
    cell = brun.cell_of(bench, args.workload)
    sys.path.insert(0, str(brun.ROOT / "src"))
    chip = device.require_tpu(cell["chips"])
    brun.use_compile_cache()
    traffic = json.loads((brun.ROOT / "bench" / "traffic" /
                          f"{cell['traffic']}.json").read_text())
    if traffic["driver"] != "serve":
        raise SystemExit(f"{args.workload} is not a serving cell")
    from bench.drivers import serve
    r = brun.Run(cell, model.load(cell["config"]), traffic, args.seed, 0.0,
                 True, chip, device.CompileCounter())
    _, pts, eng = serve.build(r)

    window = trace.Window(r.trace_dir / "fixture")
    window.start()
    with trace.span("bench.admit", window):
        for i in range(eng.queue.microbatch):
            eng.submit(pts[i])
    with trace.span("serve.step", window):
        done = eng.step()
    window.stop()
    for rid in done:
        eng.take(rid)

    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    with gzip.open(out, "wb") as f:
        f.write(pathlib.Path(window.path).read_bytes())
    red = trace.reduce(window.path)
    run = types.SimpleNamespace(
        reading={"window": window, "reduced": red}, info=lambda line: None)
    metrics = {}
    for m in brun.metrics_of(bench, args.workload, "per_layer"):
        family, _, suffix = m["name"].partition(".")
        if m["source"].startswith("program"):
            mod = importlib.import_module(f"bench.metrics.{family}")
            metrics[m["name"]] = mod.read(suffix, run)
    print(json.dumps({"out": str(out), "bytes": out.stat().st_size,
                      "answered": len(done), "busy_s": red.busy_s,
                      "idle_gaps": red.gaps(), "metrics": metrics}),
          flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
