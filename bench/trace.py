"""Profiler windows and their reduction to device time.

``Window`` starts and stops JAX's profiler around part of a run and keeps
the host-clock length of what it traced.  ``reduce`` reads the ``.xplane.pb``
the profiler wrote and returns what the per-layer metric readers
(``bench/metrics/``) need: every device operation with its start, duration
and HLO module, the busy time (the union of operation intervals), the
longest idle gaps labelled by the harness span open on the host when the
gap began, and the operations that took the most time.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import gzip
import os
import re
import shutil
import time


@contextlib.contextmanager
def span(name: str, window=None):
    """A host span visible in the trace (a no-op while nothing traces)."""
    if window is None or not window.active:
        yield
        return
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


class Window:
    """One profiler window; ``start`` and ``stop`` may be called once."""

    def __init__(self, directory):
        self.dir = str(directory)
        self.active = False
        self.seconds = 0.0
        self.path = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)
        self.active = True
        self._t0 = time.monotonic()

    def stop(self):
        import jax
        self.seconds = time.monotonic() - self._t0
        jax.profiler.stop_trace()
        self.active = False
        found = sorted(glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                                 recursive=True))
        self.path = found[-1] if found else None


@dataclasses.dataclass
class Op:
    name: str
    start_ns: int
    dur_ns: int
    module: str
    device: int
    stats: dict
    module_start: int = -1


@dataclasses.dataclass
class Reduced:
    ops: list            # [Op] of every device
    modules: list        # [Op] module executions
    spans: list          # [(name, start_ns, end_ns)] harness host spans
    devices: int
    busy_s: float        # union of op intervals, averaged over devices
    first_ns: int
    last_ns: int

    def gaps(self, top=10):
        """The longest intervals with no op running on device 0, each named
        by the innermost harness span open when it began."""
        ivs = sorted((o.start_ns, o.start_ns + o.dur_ns) for o in self.ops
                     if o.device == 0)
        out = []
        end = None
        for s, e in ivs:
            if end is not None and s > end:
                out.append((end, s))
            end = e if end is None else max(end, e)
        out.sort(key=lambda g: g[0] - g[1])
        named = []
        for a, b in out[:top]:
            label = "untraced host work"
            best = None
            for name, s, e in self.spans:
                if s <= a < e and (best is None or s >= best):
                    label, best = name, s
            named.append([label, (b - a) * 1e-9])
        return named

    def module_summary(self):
        """{module name: [executions, seconds]} over all devices."""
        out = {}
        for m in self.modules:
            c = out.setdefault(m.name, [0, 0.0])
            c[0] += 1
            c[1] += m.dur_ns * 1e-9
        return out

    def top_ops(self, top=10):
        """The operations that took the most device time, each named by
        its HLO instruction without layouts and attributes."""
        tot = {}
        for o in self.ops:
            tot[o.name] = tot.get(o.name, 0) + o.dur_ns
        return [[short_name(k), v * 1e-9 / self.devices] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:top]]


def short_name(hlo: str, limit: int = 200) -> str:
    """``%fps_blocks.4 = s32[4,1244,1,9]{...} custom-call(...), attrs`` ->
    the instruction with its shapes, without layouts and attributes."""
    text = re.sub(r"\{[^{}]*\}", "", hlo)
    text = re.split(r", (?:custom_call_target|kind|channel_id|dimensions|"
                    r"frontend_attributes|metadata)=", text)[0]
    return text[:limit]


def _union_ns(ivs):
    total, end = 0, None
    for s, e in sorted(ivs):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _device_index(plane_name: str):
    # "/device:TPU:0" -> 0 (non-TPU device planes are not read)
    if not plane_name.startswith("/device:TPU:"):
        return None
    tail = plane_name.rsplit(":", 1)[-1]
    return int(tail) if tail.isdigit() else None


OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def reduce(path: str, span_prefixes=("bench.", "serve.", "train.")
           ) -> Reduced:
    import jax
    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    else:
        pd = jax.profiler.ProfileData.from_file(str(path))
    ops, modules, spans = [], [], []
    devices = set()
    for plane in pd.planes:
        dev = _device_index(plane.name)
        if dev is None:
            if plane.name.startswith("/host"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(span_prefixes):
                            spans.append((e.name, e.start_ns,
                                          e.start_ns + e.duration_ns))
            continue
        devices.add(dev)
        mod_ivs = []
        for line in plane.lines:
            if line.name == MODULES_LINE:
                for e in line.events:
                    modules.append(Op(e.name, e.start_ns, e.duration_ns,
                                      e.name, dev, {}))
                    mod_ivs.append((e.start_ns, e.start_ns + e.duration_ns,
                                    e.name))
        mod_ivs.sort()
        starts = [m[0] for m in mod_ivs]
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                k = bisect.bisect_right(starts, e.start_ns) - 1
                inside = k >= 0 and e.start_ns < mod_ivs[k][1]
                ops.append(Op(e.name, e.start_ns, e.duration_ns,
                              mod_ivs[k][2] if inside else "", dev, {},
                              mod_ivs[k][0] if inside else -1))
    ndev = max(len(devices), 1)
    busy = sum(_union_ns([(o.start_ns, o.start_ns + o.dur_ns) for o in ops
                          if o.device == d]) for d in devices) / ndev
    starts = [o.start_ns for o in ops] or [0]
    ends = [o.start_ns + o.dur_ns for o in ops] or [0]
    return Reduced(ops, modules, spans, ndev, busy * 1e-9, min(starts),
                   max(ends))
