"""repro.serve.spans: host spans and counters on the profiler's clock.

With the profiler off a span records nothing and calls none of its
counters; with it on, the span lands in the trace with its counters as the
event's arguments, those given late (``set``) included."""
import glob
import os

import jax
import pytest

from repro.serve import spans

jax.config.update("jax_platform_name", "cpu")


def host_events(directory):
    (path,) = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                        recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return {e.name: dict(e.stats) for plane in pd.planes
            if plane.name.startswith("/host") for line in plane.lines
            for e in line.events}


def boom():
    raise AssertionError("a gated counter was computed while off")


def test_off_records_nothing_and_computes_no_counter(tmp_path):
    assert not spans.recording()
    with spans.span("serve.off", cost=boom) as sp:
        # The profiler starts inside the span: the span still records
        # nothing, and a late counter is not computed either.
        jax.profiler.start_trace(str(tmp_path))
        try:
            assert spans.recording()
            sp.set(late=boom)
            with spans.span("serve.on"):
                pass
        finally:
            jax.profiler.stop_trace()
    assert not spans.recording()
    with spans.span("serve.after", cost=boom) as sp:
        sp.set(late=boom)
    events = host_events(tmp_path)
    assert "serve.on" in events
    assert "serve.off" not in events and "serve.after" not in events


def test_on_records_counters_as_arguments(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("serve.on", n=3, lazy=lambda: 7,
                        ids=lambda: " ".join(["4", "5"])) as sp:
            sp.set(late=lambda: 0.5, plain=2)
    finally:
        jax.profiler.stop_trace()
    assert host_events(tmp_path)["serve.on"] == pytest.approx(
        {"n": 3, "lazy": 7, "ids": "4 5", "late": 0.5, "plain": 2})
