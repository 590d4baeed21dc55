"""Split a traced serving window by the host spans it holds: the device's
idle time by the innermost span the host was in, and the time of each
serving-engine span per microbatch.

    python -m bench.tools.idle_by_span bench/.trace/plugins/profile/*/*.xplane.pb

Prints one JSON line: ``idle_ms`` (ms of device-idle time under each
innermost span, "untraced host work" where none is open), ``per_microbatch_ms``
(each engine span's total over the number of ``serve.execute`` spans, and
``serve.execute``'s own time outside its children), ``count`` (spans of each
name) and ``longest_gaps`` (the ten longest gaps, each split the same way).
"""
from __future__ import annotations

import json
import sys

from bench import spans, trace

NONE = "untraced host work"


def innermost(host, t):
    """The name of the span open at ``t`` that began last."""
    best = None
    for name, s, e in host:
        if s <= t < e and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else NONE


def split(host, a, b):
    """{innermost span: ns} over the interval [a, b)."""
    cuts = sorted({a, b} | {x for _, s, e in host for x in (s, e)
                            if a < x < b})
    out = {}
    for p, q in zip(cuts, cuts[1:]):
        name = innermost(host, (p + q) / 2)
        out[name] = out.get(name, 0) + (q - p)
    return out


def main(argv=None) -> int:
    (path,) = argv if argv is not None else sys.argv[1:]
    red = trace.reduce(path)
    gaps = spans.idle_intervals(red)
    idle = {}
    longest = []
    for a, b in gaps:
        host = [s for s in red.spans if s[1] < b and s[2] > a]
        part = split(host, a, b)
        for k, v in part.items():
            idle[k] = idle.get(k, 0) + v
        longest.append((b - a, {k: v * 1e-6 for k, v in part.items()}))
    longest.sort(key=lambda g: -g[0])
    prog = spans.program(red)
    n = sum(1 for s in prog if s[0] == spans.EXECUTE)
    total, count = {}, {}
    for name, s, e in prog:
        total[name] = total.get(name, 0) + (e - s)
        count[name] = count.get(name, 0) + 1
    if n:
        kids = sum(v for k, v in total.items()
                   if k not in (spans.ADMIT, spans.EXECUTE))
        total["serve.execute (own)"] = total[spans.EXECUTE] - kids
    print(json.dumps({
        "microbatches": n, "idle_total_ms": sum(idle.values()) * 1e-6,
        "idle_ms": {k: v * 1e-6 for k, v in
                    sorted(idle.items(), key=lambda kv: -kv[1])},
        "per_microbatch_ms": {k: v * 1e-6 / n for k, v in total.items()}
        if n else {},
        "count": count,
        "longest_gaps": [[g * 1e-6, part] for g, part in longest[:10]]}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
