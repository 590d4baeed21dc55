"""The fine-tune cell's control and its half-batch fault, read by the
control tool through the committed limits: both come out not correct and
the program's own readings correct, on the CPU at a tiny size."""
import json
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import bench_cpu  # noqa: E402
from bench_cpu import ROOT  # noqa: E402

CELL = "scannet_finetune"


@pytest.fixture(scope="module")
def rows():
    from bench import compare, device, model
    from bench import run as brun
    from bench.drivers import train
    from bench.tools import control
    cell = brun.cell_of(bench_cpu.BENCH, CELL)
    cfg = dict(bench_cpu.tiny(model.load(cell["config"])), batch=4)
    tr = json.loads((ROOT / "bench" / "traffic" /
                     f"{cell['traffic']}.json").read_text())
    tr = dict(tr, clouds=dict(tr["clouds"], points=256), pool_batches=4)
    chip = device.Chip("cpu", "TPU v5 lite", 1, device.PEAKS["TPU v5 lite"])
    lims = compare.limits(cell["config"], tr["driver"])
    step = train.make_step(cfg)
    out = {}
    for quant in ("program", "fp8", "half_batch"):
        r = brun.Run(cell, cfg, tr, 2**31 + 7, 0.0, False, chip,
                     device.CompileCounter(), threads=2)
        got = control.train_readings(r, quant, step)
        out[quant] = (got, compare.judge(got, lims))
    return out


@pytest.mark.parametrize("quant", ["program", "fp8", "half_batch"])
def test_committed_limits_judge_the_readings(rows, quant):
    got, (correct, checks) = rows[quant]
    assert set(checks) <= set(got)
    assert correct is (quant == "program"), checks
