"""Without a TPU the benchmark refuses to measure; peaks are known only
for chips in its table."""
import json
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))


def test_no_tpu_exits_nonzero_and_prints_no_result(capsys):
    import jax
    from bench import run
    assert jax.default_backend() != "tpu"
    cell = json.loads((run.ROOT / "BENCHMARK.json").read_text())[
        "workloads"][0]["name"]
    rc = run.main(["--workload", cell, "--seed", str(2**31 + 5),
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out.strip() == ""
    assert "TPU" in out.err


def test_require_tpu_names_what_it_found():
    from bench import device
    with pytest.raises(device.NoChipError, match="cpu"):
        device.require_tpu(1)


def test_unknown_device_kind_is_an_error():
    from bench import device
    assert device.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert device.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(device.NoChipError, match="TPU v9"):
        device.peaks("TPU v9")


def test_memory_peak_counts_the_programs_temporaries(monkeypatch):
    import jax
    from bench import device

    class Dev:
        def __init__(self, in_use, reserved):
            self.stats = {"peak_bytes_in_use": in_use,
                          "peak_bytes_reserved": reserved}

        def memory_stats(self):
            return self.stats

    devs = [Dev(300, 1000), Dev(500, 900), Dev(10**6, 0)]
    monkeypatch.setattr(jax, "devices", lambda: devs)
    assert device.memory_peak_bytes(2) == 1400
    assert device.memory_peak_bytes(3) == 10**6
