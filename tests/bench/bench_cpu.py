"""Helpers of the fault tests: drive a cell end to end on the CPU at a tiny
size (the look for a chip is skipped), and break the timed path underneath
in the ways a cell can be broken."""
import gc
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import device, model  # noqa: E402
from bench import run as brun  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def tiny(cfg):
    """The configuration's layout at a size a CPU test can hold."""
    st = [dict(s, mlp=[8, 8, 16]) for s in cfg["sa_stages"][:2]]
    st[1]["mlp"] = [16, 16, 32]
    return dict(cfg, num_points=256, impl="xla", sa_stages=st,
                fp_mlp=[[32, 16], [16, 16]], head_mlp=[16])


def drive(cell_name, capsys, seconds=0.5):
    cell = brun.cell_of(BENCH, cell_name)
    cfg = tiny(model.load(cell["config"]))
    tr = json.loads((ROOT / "bench" / "traffic" /
                     f"{cell['traffic']}.json").read_text())
    if tr["driver"] == "serve":
        tr = dict(tr, clouds=dict(tr["clouds"], points=256, pool=4),
                  serve=dict(tr["serve"], bucket=256), check={"sample": 4},
                  arrivals=dict(tr["arrivals"], rate_per_s=16.0))
    else:
        cfg = dict(cfg, batch=4)
        tr = dict(tr, clouds=dict(tr["clouds"], points=256), pool_batches=4)
    chip = device.Chip("cpu", "TPU v5 lite", 1, device.PEAKS["TPU v5 lite"])
    r = brun.Run(cell, cfg, tr, 2**31 + 99, seconds, False, chip,
                 device.CompileCounter(), threads=2)
    try:
        __import__(f"bench.drivers.{tr['driver']}", fromlist=["run"]).run(r)
    finally:
        gc.unfreeze()       # set-up froze the test process's objects
    capsys.readouterr()
    assert brun.report(r, BENCH, 0) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_compilation_cache_dir",
                      str(tmp_path_factory.mktemp("jax_cache")))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cc.reset_cache()
    yield
    jax.config.update("jax_compilation_cache_dir", was[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", was[1])
    cc.reset_cache()


def alter_answer(monkeypatch):
    from repro.serve import engine
    fwd = engine.ServeEngine.forward

    def forward(self, *a):
        out = fwd(self, *a)
        return out.at[0].add(0.2 * jnp.max(jnp.abs(out)))
    monkeypatch.setattr(engine.ServeEngine, "forward", forward)


def half_microbatch(monkeypatch):
    from repro.serve import engine
    fwd = engine.ServeEngine.forward

    def forward(self, bucket, clouds, valid, dim0):
        out = fwd(self, bucket, clouds, valid, dim0)
        half = out.shape[0] // 2
        return out.at[half:].set(0.0)
    monkeypatch.setattr(engine.ServeEngine, "forward", forward)


def unchanged_state(monkeypatch):
    from repro.train import pnn as tpnn
    make = tpnn.make_train_step

    def make_train_step(*a, **k):
        step = make(*a, **k)

        def frozen(params, opt_state, batch, return_grads=False):
            _, _, metrics = step(params, opt_state, batch)
            return params, opt_state, metrics
        return frozen
    monkeypatch.setattr(tpnn, "make_train_step", make_train_step)


def half_batch(monkeypatch):
    from repro.train import pnn as tpnn
    make = tpnn.make_train_step

    def make_train_step(*a, **k):
        step = make(*a, **k)

        def half(params, opt_state, batch, return_grads=False):
            b = batch["points"].shape[0] // 2
            return step(params, opt_state,
                        {k_: v[:b] for k_, v in batch.items()})
        return half
    monkeypatch.setattr(tpnn, "make_train_step", make_train_step)


