"""The serving engine's own spans, as the benchmark reads them: a tiny
engine traced on the CPU inside the harness's ``serve.step`` span yields
every span of the engine with its arguments, and the readers of
``host_ms``, ``queue_wait_ms`` and ``leaf_fill`` give the numbers those
spans hold; on a trace without them every reader gives ``None``."""
import pathlib
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

FIXTURES = pathlib.Path(__file__).parent / "fixtures"
BUCKET, TH, MICROBATCH = 64, 32, 2
# Five clouds (submit times, injected): the first step takes two full
# microbatches and leaves the fifth cloud queued until the flush.
SIZES = (64, 50, 40, 64, 33)
T_SUBMIT = (10.0, 10.1, 10.2, 10.3, 10.6)
T_STEP, T_FLUSH = 11.0, 12.0


def clouds():
    import numpy as np
    return [np.random.default_rng(i).random((n, 3), np.float32)
            for i, n in enumerate(SIZES)]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from bench import trace
    from repro import serve
    eng = serve.ServeEngine(serve.ServeConfig(
        buckets=(BUCKET,), microbatch=MICROBATCH, max_wait_s=1.0,
        task="seg", num_classes=4, th=TH, impl="xla",
        on_overflow="silent"))
    eng.warm()
    # The eager ops of a full and of a partial microbatch compile here, as
    # in the serve driver's set-up, so that the trace holds no compile.
    cs = clouds()
    for k in (MICROBATCH, 1):
        for c in cs[:k]:
            eng.submit(c, now=0.0)
        eng.flush(now=0.0)
    window = trace.Window(tmp_path_factory.mktemp("trace"))
    window.start()
    try:
        with trace.span("serve.step", window):
            rids = [eng.submit(c, now=t) for c, t in zip(cs, T_SUBMIT)]
            done = eng.step(now=T_STEP) + eng.flush(now=T_FLUSH)
    finally:
        window.stop()
    assert done == rids
    return types.SimpleNamespace(
        reading={"window": window, "reduced": trace.reduce(window.path)},
        rids=rids, info=lambda line: None)


def fake_run(path):
    from bench import trace
    return types.SimpleNamespace(
        reading={"window": types.SimpleNamespace(path=str(path)),
                 "reduced": trace.reduce(path)},
        info=lambda line: None)


def test_every_span_nests_in_the_harness_span(served):
    from bench import spans
    red = served.reading["reduced"]
    (step,) = [s for s in red.spans if s[0] == "serve.step"]
    prog = spans.program(red)
    assert {n for n, _, _ in prog} == set(spans.PROGRAM)
    assert all(step[1] <= s and e <= step[2] for _, s, e in prog)
    names = [n for n, _, _ in prog]
    assert names.count("serve.admit") == len(SIZES)
    for name in spans.PROGRAM[1:]:
        assert names.count(name) == 3, name       # three microbatches
    # Each microbatch's children nest in its serve.execute, in order.
    for ex in [s for s in prog if s[0] == "serve.execute"]:
        kids = sorted((s for s in prog if s[0] not in spans.PROGRAM[:2]
                       and ex[1] <= s[1] and s[2] <= ex[2]),
                      key=lambda s: s[1])
        assert [k[0] for k in kids] == list(spans.PROGRAM[2:])


def test_spans_carry_their_arguments(served):
    from bench import spans
    admits = spans.args(served, "serve.admit")
    assert [(a["rid"], a["bucket"]) for a in admits] == [
        (rid, BUCKET) for rid in served.rids]
    # The spans of one request share its id.
    ex = spans.args(served, "serve.execute")
    r = [str(rid) for rid in served.rids]
    assert [str(a["rids"]).split() for a in ex] == [r[:2], r[2:4], r[4:]]
    assert [a["requests"] for a in ex] == [2, 2, 1]
    assert [a["slots"] for a in ex] == [MICROBATCH] * 3
    # Requests still waiting when each microbatch is dispatched: the
    # later full microbatch taken by the same step, and the queued fifth.
    assert [a["depth"] for a in ex] == [3, 1, 0]
    waits = [1e3 * ((T_STEP - T_SUBMIT[0]) + (T_STEP - T_SUBMIT[1])),
             1e3 * ((T_STEP - T_SUBMIT[2]) + (T_STEP - T_SUBMIT[3])),
             1e3 * (T_FLUSH - T_SUBMIT[4])]
    assert [a["wait_ms"] for a in ex] == pytest.approx(waits)


def test_leaves_equal_the_partition_of_the_padded_clouds(served):
    import jax
    import numpy as np
    from bench import spans
    from repro import core
    from repro.kernels import ops as kops
    plan = jax.jit(lambda c, v: core.partition(c, v, th=TH,
                                               on_overflow="silent"))
    parts = [plan(*kops.pad_points(c, BUCKET)) for c in clouds()]
    leaves = [int(p.num_leaves) for p in parts]
    ml = parts[0].leaf_start.shape[-1]
    ex = spans.args(served, "serve.execute")
    assert [a["leaves"] for a in ex] == [leaves[0] + leaves[1],
                                         leaves[2] + leaves[3], leaves[4]]
    assert [a["leaf_slots"] for a in ex] == [2 * ml, 2 * ml, ml]
    from bench.metrics import leaf_fill
    assert leaf_fill.read("serve_max", served) == pytest.approx(
        100.0 * np.sum(leaves) / (len(SIZES) * ml))


def test_host_and_queue_readers(served):
    from bench.metrics import host_ms, queue_wait_ms
    red = served.reading["reduced"]
    total = {n: sum(e - s for m, s, e in red.spans if m == n)
             for n in ("serve.admit", "serve.execute", "serve.sync")}
    want = (total["serve.admit"] + total["serve.execute"]
            - total["serve.sync"]) * 1e-6 / 3
    assert host_ms.read("serve_max", served) == pytest.approx(want)
    waits = ((T_STEP - T_SUBMIT[0]) + (T_STEP - T_SUBMIT[1])
             + (T_STEP - T_SUBMIT[2]) + (T_STEP - T_SUBMIT[3])
             + (T_FLUSH - T_SUBMIT[4]))
    assert queue_wait_ms.read("serve_max", served) == pytest.approx(
        1e3 * waits / len(SIZES))


@pytest.mark.parametrize("family", ["host_ms", "queue_wait_ms", "leaf_fill",
                                    "idle_explained"])
def test_readers_give_none_without_program_spans(family):
    """The trace of a program without the spans (a chip trace of one
    microbatch, harness spans only)."""
    import importlib
    mod = importlib.import_module(f"bench.metrics.{family}")
    assert mod.read("serve_max",
                    fake_run(FIXTURES / "serve.xplane.pb.gz")) is None


def test_idle_share_covered_by_spans():
    from bench import spans
    gaps = [(0, 10), (20, 30), (40, 50)]
    assert spans.covered_ns(gaps, [(5, 25), (22, 24), (45, 60)]) == 15
    assert spans.covered_ns(gaps, []) == 0
    assert spans.covered_ns(gaps, [(-5, 100)]) == 30


# A chip trace of one microbatch of s3dis_serve_overload (4 blocks of
# 4,096 points, th 32) with the program's spans, recorded on a TPU v5 lite
# by ``python -m bench.tools.fixture``.
CHIP = FIXTURES / "serve_spans.xplane.pb.gz"


@pytest.fixture(scope="module")
def chip():
    return fake_run(CHIP)


def test_chip_fixture_idle_explained(chip):
    from bench.metrics import idle_explained
    assert idle_explained.read("serve_max", chip) == pytest.approx(
        98.15130736513039, rel=1e-9)


def test_chip_fixture_gaps_start_inside_program_spans(chip):
    """Host spans and device operations share one clock: each of the five
    longest device-idle gaps begins while the host is inside one of the
    engine's spans."""
    from bench import spans
    red = chip.reading["reduced"]
    prog = spans.program(red)
    gaps = sorted(spans.idle_intervals(red), key=lambda g: g[0] - g[1])
    assert len(gaps) >= 5
    for a, b in gaps[:5]:
        assert any(s <= a < e for _, s, e in prog), (a, b)


def test_chip_fixture_metrics(chip):
    from bench import spans
    from bench.metrics import host_ms, leaf_fill, queue_wait_ms
    assert host_ms.read("serve_max", chip) == pytest.approx(15.432551)
    assert queue_wait_ms.read("serve_max", chip) > 0
    # 4 clouds x 1,244 stage-0 slots at th 32.
    (ex,) = spans.args(chip, "serve.execute")
    assert ex["leaf_slots"] == 4 * 1244 and ex["requests"] == 4
    assert leaf_fill.read("serve_max", chip) == pytest.approx(
        100.0 * ex["leaves"] / (4 * 1244))
