"""The Pallas kernels through the TPU compiler, without a TPU.

Interpret mode (every other kernel test) cannot see what only the chip's
compiler refuses: block shapes off the (8, 128) tiling, vector ops Mosaic
does not lower, VMEM overruns.  Here each kernel of the main path is
AOT-compiled for one chip of a described ``v5e:2x2`` topology at the block
sizes the default serving config hands it (th=256, the ``PNNConfig``
stages), vmapped over a microbatch as the serve executable runs it, and the
executable must hold a Mosaic kernel (``tpu_custom_call``).

The neighbour searches' layout is checked at the benchmark's sizes too:
query rows padded to a multiple of 8, so the custom call's centre operand
ends in (3, R) and its index result in (R, num) — the shapes the
benchmark's roofline reads — while several leaves share each grid step.

The topology is described inside a fixture, never on import: only one
process may load the TPU library, and every test worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ball_query, fps, fractal_engine, gather, knn, ops
from repro.models import pnn
from repro.serve import ServeConfig

MICROBATCH = 4
NB = 151          # leaves of one 16,384-point cloud at th=256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def sizes():
    """Block sizes of the default serving path (kernels/ops.py pads point
    axes to 128 lanes, ball-query centers and window rows to 8 sublanes)."""
    th = ServeConfig().th
    stage = pnn.PNNConfig().stages[0]
    lanes = lambda n: -(-n // 128) * 128  # noqa: E731
    k = int(round(stage.rate * th)) + 1
    return {"bs": th, "k": k, "kc": -(-k // 8) * 8, "w": 2 * th,
            "num": stage.nsample, "radius": stage.radius,
            "wc": lanes(max(16, int(2 * th * stage.rate))),
            "m": 3 * th, "c": lanes(max(max(s.widths) for s in
                                        pnn.PNNConfig().stages))}


def _compile(topo, fn, *shapes):
    one_chip = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct((MICROBATCH, NB) + s, d, sharding=one_chip)
            for s, d in shapes]
    return jax.jit(jax.vmap(fn)).lower(*args).compile()


F32, I32 = jnp.float32, jnp.int32

CASES = {
    "fps": lambda z: (
        lambda c, m: fps.fps_blocks(c, m, k=z["k"], interpret=False),
        ((3, z["bs"]), F32), ((1, z["bs"]), F32)),
    "ball_query": lambda z: (
        lambda c, cm, w, wm: ball_query.ball_query_blocks(
            c, cm, w, wm, radius=z["radius"], num=z["num"],
            interpret=False),
        ((3, z["kc"]), F32), ((1, z["kc"]), F32),
        ((3, z["w"]), F32), ((1, z["w"]), F32)),
    "knn": lambda z: (
        lambda q, w, wm: knn.knn_blocks(q, w, wm, k=3, interpret=False),
        ((3, z["bs"]), F32), ((3, z["wc"]), F32), ((1, z["wc"]), F32)),
    "gather": lambda z: (
        lambda f, i: gather.gather_blocks(f, i, interpret=False),
        ((z["wc"], z["c"]), F32), ((z["m"],), I32)),
    "scatter_add": lambda z: (
        lambda g, i: gather.scatter_add_blocks(g, i, w=z["wc"],
                                               interpret=False),
        ((z["m"], z["c"]), F32), ((z["m"],), I32)),
    "fractal_level": lambda z: (
        lambda c, m, mid: fractal_engine.fractal_level_blocks(
            c, m, mid, da=0, db=1, interpret=False),
        ((3, z["bs"]), F32), ((1, z["bs"]), F32), ((1,), F32)),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(topo, sizes, name):
    fn, *shapes = CASES[name](sizes)
    compiled = _compile(topo, fn, *shapes)
    assert "tpu_custom_call" in compiled.as_text()


# The benchmark's stage-1 neighbour searches: S3DIS ball query (9 centers
# per leaf, 1,244 leaf slots, microbatch 4), ScanNet's (5 centers, 2,488
# slots, batch 16) and the first FP stage's kNN (32 points of a fine leaf).
LAYOUT = {
    "ball_query_s3dis": dict(mb=4, nb=1244, rows=9, w=64, num=32, r=16),
    "ball_query_scannet": dict(mb=16, nb=2488, rows=5, w=64, num=32, r=8),
    "knn_fp": dict(mb=4, nb=1244, rows=32, w=16, num=3, r=32),
}


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The wrappers compile their kernels, not the interpreter the CPU
    backend would pick; the traces made so are dropped afterwards."""
    monkeypatch.setattr(ops, "interpret_kernels", lambda: False)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _custom_call_shapes(text):
    """(result shapes, operand shapes) of the one tpu_custom_call."""
    shape = lambda t: [tuple(int(x) for x in m.split(",") if x)  # noqa: E731
                       for m in re.findall(r"(?:f32|s32)\[([0-9,]*)\]", t)]
    [line] = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    results = line.split("custom-call(")[0]
    operands = line.split("operand_layout_constraints={")[1].split("}}")[0]
    return shape(results), shape(operands)


@pytest.mark.parametrize("name", sorted(LAYOUT))
def test_neighbour_search_packs_rows(topo, compiled_kernels, name):
    z = LAYOUT[name]
    one_chip = SingleDeviceSharding(topo.devices[0])
    lead = (z["mb"], z["nb"])

    def arg(shape, dtype=F32):
        return jax.ShapeDtypeStruct(lead + shape, dtype, sharding=one_chip)

    rows, win = arg((z["rows"], 3)), arg((z["w"], 3))
    rmask, wmask = arg((z["rows"],), jnp.bool_), arg((z["w"],), jnp.bool_)
    if name.startswith("ball_query"):
        fn = lambda c, cm, w, wm: ops.ball_query_blocks(  # noqa: E731
            c, cm, w, wm, radius=0.1, num=z["num"], impl="pallas")
        args = (rows, rmask, win, wmask)
    else:
        fn = lambda q, w, wm: ops.knn_blocks(  # noqa: E731
            q, w, wm, k=z["num"], impl="pallas")
        args = (rows, win, wmask)
    compiled = jax.jit(jax.vmap(fn)).lower(*args).compile()
    results, operands = _custom_call_shapes(compiled.as_text())
    r, g = z["r"], 128 // z["r"]
    assert operands[0][-2:] == (3, r)
    assert results[0][-2:] == (r, z["num"])
    assert operands[0][:2] == (z["mb"], -(-z["nb"] // g) * g)
