"""Run one benchmark cell once, on the chip this process finds.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name, starting from the cell's entry in
``BENCHMARK.json``:

* the configuration ``bench/configs/<config>.json`` (sizes of the network);
* the traffic mix ``bench/traffic/<traffic>.json``, whose ``driver`` key
  names the module of ``bench/drivers/`` that drives it;
* each per-layer metric ``<family>.<suffix>`` is read by
  ``bench/metrics/<family>.py``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` in a
traced run), then ``checks``, each number compared beside its limit.  With
no TPU, or fewer chips than the cell asks for, the run exits with code 3
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import concurrent.futures  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / "bench" / ".jax_cache"
TRACE_DIR = ROOT / "bench" / ".trace"
EXIT_NO_CHIP = 3


def cell_of(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def metrics_of(bench: dict, cell: str, kind: str) -> list:
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def use_compile_cache():
    """JAX's persistent compilation cache at a fixed path in the checkout,
    whatever the environment says, and for every program, so that only the
    first run of a cell compiles."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Run:
    """What a driver is handed: the cell's files, the seed, the window, and
    the calls through which it reports."""

    def __init__(self, cell, cfg, traffic, seed, seconds, trace, chip,
                 compiles, t_start=T_START, threads=8):
        import jax
        from bench import clouds
        self.cell, self.cfg, self.traffic = cell, cfg, traffic
        self.seed, self.seconds, self.trace = seed, float(seconds), trace
        self.chip, self.compiles = chip, compiles
        self.key = jax.random.PRNGKey(
            int(clouds.rng_for(seed, 100).integers(0, 2**31 - 1)))
        self.trace_dir = TRACE_DIR
        self.trace_seconds = float(traffic.get("trace_seconds", 3.0))
        self.threads = threads
        self.t_start = t_start
        self.e2e, self.values, self.lines = {}, {}, []
        self.attempted = self.failed = 0
        self.setup_s = self.window_compiles = None
        self.peak_bytes = 0
        self.reading = None

    # -- reporting, in the order a driver calls them ----------------------

    def setup_done(self):
        # Set-up leaves millions of objects behind (the traced programs);
        # collect them now and exempt the survivors from later collections,
        # so that no full collection of them pauses the window.
        gc.collect()
        gc.freeze()
        self.setup_s = time.monotonic() - self.t_start
        self.e2e["setup_s"] = self.setup_s
        self.compiles.mark()
        self.setup_compiles = (self.compiles.n, self.compiles.s,
                               self.compiles.hits, self.compiles.misses)

    def window_done(self):
        self.window_compiles = self.compiles.since_mark()

    def memory_peak(self):
        from bench import device
        self.peak_bytes = device.memory_peak_bytes(self.chip.count)

    def info(self, line: str):
        self.lines.append(line)

    def traced(self, window, units, work_flops):
        from bench import trace
        red = trace.reduce(window.path) if window.path else None
        self.reading = {"window": window, "units": units,
                        "work_flops": work_flops, "reduced": red}
        if red is not None:
            mods = sorted(red.module_summary().items(),
                          key=lambda kv: -kv[1][1])[:8]
            self.info(f"trace ops={len(red.ops)} modules={mods}")

    def check(self, values: dict):
        self.values.update(values)

    # -- the reference, run after the window -------------------------------

    def map(self, fn, items):
        with concurrent.futures.ThreadPoolExecutor(self.threads) as ex:
            return list(ex.map(fn, items))

    def reference_logits(self, params, coords, plans, quant=None, block=8):
        """Reference logits of clouds (B, n, 3) with their plans, in
        blocks of ``block`` clouds."""
        import jax
        import numpy as np
        from bench import reference
        fwd = jax.jit(lambda p, c, pl: reference.forward(p, c, pl, quant))
        out = []
        for i in range(0, len(plans), block):
            pl = reference.stack_plans(plans[i:i + block])
            out.append(np.asarray(fwd(params, coords[i:i + block], pl)))
        return np.concatenate(out) if out else np.zeros((0,))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = cell_of(bench, args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    from bench import device, model
    try:
        chip = device.require_tpu(cell["chips"])
    except device.NoChipError as e:
        print(f"bench: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    use_compile_cache()
    cfg = model.load(cell["config"])
    traffic = json.loads((ROOT / "bench" / "traffic" /
                          f"{cell['traffic']}.json").read_text())
    run = Run(cell, cfg, traffic, args.seed, args.seconds, bool(args.trace),
              chip, device.CompileCounter())
    driver = importlib.import_module(f"bench.drivers.{traffic['driver']}")
    driver.run(run)
    return report(run, bench, args.trace)


def report(run: Run, bench: dict, trace: int) -> int:
    from bench import compare
    name = run.cell["name"]
    lims = compare.limits(run.cell["config"], run.traffic["driver"])
    correct, checks = compare.judge(run.values, lims)
    dev = dict(run.chip.describe(), memory_peak_bytes=int(run.peak_bytes))
    out = {"correct": correct, "attempted": int(run.attempted),
           "failed": int(run.failed)}
    metrics = {}
    if not trace:
        for m in metrics_of(bench, name, "end_to_end"):
            metrics[m["name"]] = {"value": run.e2e[m["name"]],
                                  "unit": m["unit"]}
    else:
        rd = run.reading or {}
        red = rd.get("reduced")
        window = rd.get("window")
        for m in metrics_of(bench, name, "per_layer"):
            family, _, suffix = m["name"].partition(".")
            mod = importlib.import_module(f"bench.metrics.{family}")
            v = mod.read(suffix, run) if red is not None else None
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if red is not None:
            dev["busy_s"] = red.busy_s
            dev["window_s"] = window.seconds
            out["breakdown"] = {"device_ops": red.top_ops(),
                                "idle_gaps": red.gaps()}
    out["metrics"] = metrics
    out["device"] = dev
    out["checks"] = checks
    n, s = run.window_compiles or (0, 0.0)
    for line in run.lines:
        print(f"[{name}] {line}", file=sys.stderr)
    c = run.setup_compiles
    print(f"[{name}] setup_s={run.setup_s} setup_executables={c[0]} "
          f"setup_compile_s={c[1]} cache_hits={c[2]} cache_misses={c[3]} "
          f"window_executables={n} window_compile_s={s}", file=sys.stderr)
    for k, v in checks.items():
        print(f"check {k}={v['value']!r} limit={v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
