"""The reduction from a profiler trace to per-layer numbers, on a trace of
one serve microbatch (4 blocks of 4,096 points, the s3dis configuration)
recorded on a TPU v5 lite and kept as a fixture."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "serve.xplane.pb.gz"


@pytest.fixture(scope="module")
def red():
    from bench import trace
    return trace.reduce(FIXTURE)


def test_device_ops_and_busy_time(red):
    from bench import trace
    assert red.devices == 1 and len(red.ops) > 100
    span = (red.last_ns - red.first_ns) * 1e-9
    total = sum(o.dur_ns for o in red.ops) * 1e-9
    # Busy is the union of op intervals: no more than the span, no more
    # than the sum of durations, and the same as a direct union.
    assert 0 < red.busy_s <= span + 1e-9
    assert red.busy_s <= total + 1e-9
    ivs = sorted((o.start_ns, o.start_ns + o.dur_ns) for o in red.ops)
    assert trace._union_ns(ivs) * 1e-9 == pytest.approx(red.busy_s)


def test_union_of_overlapping_intervals():
    from bench import trace
    assert trace._union_ns([(0, 10), (5, 20), (30, 40), (35, 36)]) == 30


def test_kernel_calls_carry_their_shapes(red):
    from bench import layers
    calls = layers.kernel_calls(red)
    kinds = {c.kind for c in calls}
    assert {"fps", "ball_query", "knn", "gather"} <= kinds
    for c in calls:
        d = layers.dims(c)
        assert all(v > 0 for v in d.values()), (c.kind, d)
    fps = [layers.dims(c) for c in calls if c.kind == "fps"]
    # The first stage of a 4-cloud microbatch: blocks of 128 lanes.
    assert all(d["bs"] % 128 == 0 for d in fps)
    assert max(d["nb"] for d in fps) % 4 == 0


def test_roofline_share_is_a_share(red):
    from bench import device, layers
    calls = layers.kernel_calls(red)
    least, spent, bounds = layers.roofline(calls,
                                           device.PEAKS["TPU v5 lite"])
    assert 0 < least < spent
    assert sum(bounds.values()) == len(calls)


def test_plan_and_forward_are_told_apart(red):
    from bench import layers
    assert len(layers.serve_runs(red)) == 1
    assert len(layers.plan_runs(red)) == 1


def test_breakdown(red):
    top = red.top_ops()
    assert len(top) == 10
    assert all(a[1] >= b[1] for a, b in zip(top, top[1:]))
    gaps = red.gaps()
    assert 0 < len(gaps) <= 10
    assert all(isinstance(label, str) and s > 0 for label, s in gaps)
