"""The chip: which one this is, its published peaks, its memory, and a
count of the backend compiles made while a run is measured.

Peaks (one table, keyed by ``device_kind`` as JAX reports it).  Source:
Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
16 GB HBM at 819 GB/s.  A kind that is not in the table is an error.
"""
from __future__ import annotations

import dataclasses

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


class NoChipError(RuntimeError):
    """No TPU, too few chips, or a chip of a kind with no known peaks."""


def peaks(kind: str) -> dict:
    if kind not in PEAKS:
        raise NoChipError(f"no published peaks for device kind {kind!r}; "
                          f"known: {sorted(PEAKS)}")
    return PEAKS[kind]


@dataclasses.dataclass(frozen=True)
class Chip:
    platform: str
    kind: str
    count: int
    peaks: dict

    def describe(self) -> dict:
        return {"platform": self.platform, "kind": self.kind,
                "count": self.count}


def require_tpu(chips: int) -> Chip:
    """The TPU devices this run may use, or NoChipError."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChipError(f"no accelerator: {e}") from e
    platform = devs[0].platform
    if platform != "tpu":
        raise NoChipError(f"this benchmark measures a TPU; JAX found "
                          f"{len(devs)} {platform} device(s)")
    if len(devs) < chips:
        raise NoChipError(f"the cell needs {chips} chips; JAX found "
                          f"{len(devs)}")
    kind = devs[0].device_kind
    return Chip(platform, kind, chips, peaks(kind))


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes held on the fullest of the run's chips: the peak of the
    buffers in use (arrays and loaded programs) plus the peak of the region
    the runtime reserves for the programs' temporaries, which
    ``peak_bytes_in_use`` leaves out."""
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)) +
                     int(st.get("peak_bytes_reserved", 0)))
    return max(peaks)


class CompileCounter:
    """Counts, through JAX's monitoring events, the executables a run
    obtains (``n``, ``s``: each backend compile or persistent-cache load,
    and its seconds) and how many of them the persistent cache served
    (``hits``) or could not (``misses``); ``mark()`` opens a new section so
    that set-up and window can be told apart."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n, self.s, self.hits, self.misses = 0, 0.0, 0, 0
        self._mark = (0, 0.0)
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration_secs, **_):
        if event == self.EVENT:
            self.n += 1
            self.s += duration_secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self):
        self._mark = (self.n, self.s)

    def since_mark(self):
        return self.n - self._mark[0], self.s - self._mark[1]
