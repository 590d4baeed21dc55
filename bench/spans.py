"""The serving engine's own spans in a traced window, for the readers of
``bench/metrics/``.

The engine (``repro/serve/engine.py``) opens a host span at each of its
boundaries while the profiler runs; the names are listed here so that the
benchmark imports nothing of the program.  Names and times come from the
reduced trace (``Reduced.spans``), the spans' arguments from the trace file
itself.  The profiler records a span only if it opened and closed while it
ran, so every span read lies wholly inside the traced window.  A microbatch
whose ``serve.execute`` was still open when the profiler stopped has no
``serve.execute`` span; ``executions`` leaves out its children too.
"""
from __future__ import annotations

import functools
import gzip

ADMIT = "serve.admit"
EXECUTE = "serve.execute"
SYNC = "serve.sync"
PROGRAM = (ADMIT, EXECUTE, "serve.assemble", "serve.plan", "serve.forward",
           SYNC, "serve.fetch")


def program(red) -> list:
    """[(name, start_ns, end_ns)] of the program's spans."""
    return [s for s in red.spans if s[0] in PROGRAM]


def executions(red) -> list:
    """[(execute span, its sync span)] of every microbatch recorded whole."""
    execs = sorted(s for s in red.spans if s[0] == EXECUTE)
    syncs = sorted(s for s in red.spans if s[0] == SYNC)
    out = []
    for ex in execs:
        inner = [s for s in syncs if ex[1] <= s[1] and s[2] <= ex[2]]
        if inner:
            out.append((ex, inner[0]))
    return out


def args(run, name: str) -> list:
    """The arguments of every span ``name`` in the traced window's file, in
    the order the spans began."""
    path = run.reading["window"].path
    return [a for n, _, a in _host_events(str(path)) if n == name]


@functools.lru_cache(maxsize=2)
def _host_events(path: str) -> tuple:
    import jax
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    else:
        pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in PROGRAM:
                    out.append((e.name, e.start_ns, dict(e.stats)))
    return tuple(sorted(out, key=lambda ev: ev[1]))


def idle_intervals(red) -> list:
    """[(start_ns, end_ns)] of every gap between operations on device 0."""
    ivs = sorted((o.start_ns, o.start_ns + o.dur_ns) for o in red.ops
                 if o.device == 0)
    out, end = [], None
    for s, e in ivs:
        if end is not None and s > end:
            out.append((end, s))
        end = e if end is None else max(end, e)
    return out


def covered_ns(gaps, spans) -> float:
    """Time of ``gaps`` inside at least one of ``spans`` (both lists of
    (start, end) pairs; the gaps do not overlap)."""
    merged = []
    for s, e in sorted(spans):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    total, k = 0.0, 0
    for a, b in sorted(gaps):
        while k < len(merged) and merged[k][1] <= a:
            k += 1
        j = k
        while j < len(merged) and merged[j][0] < b:
            total += min(b, merged[j][1]) - max(a, merged[j][0])
            j += 1
    return total
