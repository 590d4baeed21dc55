"""The control of a cell's correctness limits: the reference put in the
program's place, computed from float8 (e4m3) operands -- the precision
below the configuration's one-bfloat16-pass matmuls -- at the cell's own
sizes, read by the same numbers the cell compares and judged by the
committed limits (``correct``, which the control has to fail).  Its
smallest reading over the seeds is the upper reading of a limit.  For a
fine-tune cell the tool also reads the half-batch fault and, in one
process, the program's own numbers over many seeds (the lower readings).

    python -m bench.tools.control --workload s3dis_serve_overload \
        --seeds 1,2,3
    python -m bench.tools.control --workload scannet_finetune \
        --seeds 1,2,3 --quant half_batch

Prints one JSON line per seed and writes them to
``<out>/control.<cell>.json`` (``--out``, default ``bench/.results``).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

from bench import clouds, compare, device, model, reference
from bench import run as brun


def serve_control(r, quant):
    tr, cfg = r.traffic, r.cfg
    pts, _ = clouds.pool(r.seed, tr["clouds"])
    pick = clouds.rng_for(r.seed, 103).choice(len(pts), tr["check"]["sample"],
                                              replace=False)
    params = model.weights(cfg, r.key)
    cs = pts[pick]
    plans = r.map(lambda c: reference.plan_cloud(c, np.ones(len(c), bool),
                                                 cfg), list(cs))
    want = r.reference_logits(params, cs, plans)
    got = r.reference_logits(params, cs, plans, quant=quant)
    return {"logit_gap": max(compare.logit_gap(g, w)
                             for g, w in zip(got, want))}


def train_readings(r, quant, step=None):
    """The fine-tune cell's numbers on one seed: of the program (``quant``
    "program": ``step`` driven through its first steps as the cell drives
    it), of the control (the reference from ``quant`` operands) or of the
    fault of a step that takes the mean over half of its batch
    ("half_batch", the reference on the first half of each batch)."""
    import jax
    from bench.drivers import train
    cfg, k = r.cfg, r.traffic["checked_steps"]
    pts, lab = train.pool(r)
    params = model.weights(cfg, r.key)
    want = train.reference_first(r, params, pts, lab)
    if quant == "program":
        batches = [{"points": jax.device_put(pts[i]),
                    "labels": jax.device_put(lab[i])} for i in range(k)]
        _, _, got = train.first_steps(cfg, *step, params, batches, k)
    elif quant == "half_batch":
        got = train.reference_first(r, params, pts, lab,
                                    rows=pts.shape[1] // 2)
    else:
        got = train.reference_first(r, params, pts, lab, quant=quant)
    return train.numbers(r, got, want)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--quant", default="fp8",
                    help="comma-separated: fp8 (the control); for "
                         "fine-tune cells also half_batch (a fault) and "
                         "program (the program's own readings)")
    ap.add_argument("--out", default=str(brun.ROOT / "bench" / ".results"))
    args = ap.parse_args(argv)
    bench = json.loads((brun.ROOT / "BENCHMARK.json").read_text())
    cell = brun.cell_of(bench, args.workload)
    sys.path.insert(0, str(brun.ROOT / "src"))
    chip = device.require_tpu(cell["chips"])
    brun.use_compile_cache()
    traffic = json.loads((brun.ROOT / "bench" / "traffic" /
                          f"{cell['traffic']}.json").read_text())
    cfg = model.load(cell["config"])
    lims = compare.limits(cell["config"], traffic["driver"])
    quants = args.quant.split(",")
    step = None
    if traffic["driver"] == "train" and "program" in quants:
        from bench.drivers import train
        step = train.make_step(cfg)
    rows = []
    for quant in quants:
        for seed in [int(s) for s in args.seeds.split(",")]:
            r = brun.Run(cell, cfg, traffic, seed, 0.0, False, chip,
                         device.CompileCounter())
            if traffic["driver"] == "serve":
                got = serve_control(r, quant)
            else:
                got = train_readings(r, quant, step)
            correct, _ = compare.judge(got, lims)
            row = {"workload": args.workload, "seed": seed, "quant": quant,
                   **got, "correct": correct}
            rows.append(row)
            for line in r.lines:
                print(f"[{quant} {seed}] {line}", file=sys.stderr)
            print(json.dumps(row), flush=True)
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    (out / f"control.{args.workload}.json").write_text(json.dumps(rows))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
