"""The harness comes out correct on the sound program and not correct with
the timed path broken underneath (fine-tuning: a step that returns its state unchanged, half of the batch left out (the mean taken over the rest)), driven end to end on the CPU at a
tiny size."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import bench_cpu  # noqa: E402
from bench_cpu import cache  # noqa: E402,F401


@pytest.mark.parametrize("cell,fault", [
    ("scannet_finetune", None),
    ("scannet_finetune", bench_cpu.unchanged_state),
    ("scannet_finetune", bench_cpu.half_batch),
], ids=lambda x: getattr(x, "__name__", x) or "sound")
def test_correct_only_when_sound(cell, fault, cache, monkeypatch, capsys):
    if fault is not None:
        fault(monkeypatch)
    out = bench_cpu.drive(cell, capsys)
    assert out["correct"] is (fault is None), out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0
