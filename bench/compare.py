"""The numbers that decide ``correct``, and their limits.

Each cell compares what its timed path returned with the plain reference
(``bench/reference.py``).  The limit of every number lives in
``bench/limits/<config>.<driver>.json``, with the readings it was set from;
``PERF.md`` gives the same readings.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

LIMITS = pathlib.Path(__file__).resolve().parent / "limits"


def limits(config: str, driver: str) -> dict:
    data = json.loads((LIMITS / f"{config}.{driver}.json").read_text())
    return {k: v["limit"] for k, v in data.items()}


def logit_gap(got, want) -> float:
    """Widest distance between served and reference logits, over the
    reference's largest magnitude in the same cloud."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want)) /
                 max(float(np.max(np.abs(want))), 1e-30))


def leaf_gaps(got: dict, want: dict, exclude=()) -> dict:
    """Per leaf, ``| |got_leaf| - |want_leaf| |`` over the larger of
    ``|want_leaf|`` and the median leaf norm of ``want``."""
    norms = {k: float(np.linalg.norm(np.asarray(v, np.float64)))
             for k, v in want.items()}
    med = float(np.median([v for k, v in norms.items() if k not in exclude]))
    out = {}
    for k in want:
        if k in exclude:
            continue
        g = float(np.linalg.norm(np.asarray(got[k], np.float64)))
        out[k] = (abs(g - norms[k]) / max(norms[k], med, 1e-30)
                  if np.isfinite(g) else float("inf"))
    return out


def judge(values: dict, lims: dict) -> tuple[bool, dict]:
    """Whether every number is within its limit, and the numbers beside
    their limits (in the order of ``lims``)."""
    out, ok = {}, True
    for name, lim in lims.items():
        v = float(values[name])
        out[name] = {"value": v, "limit": lim}
        ok = ok and bool(np.isfinite(v)) and v <= lim
    return ok, out
