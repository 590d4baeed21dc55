"""Which device operations belong to which layer of the program.

The readers of ``bench/metrics/`` ask this module for the point-op kernel
calls of a reduced trace (each with its kind and shapes), the executions of
the partition plan, and the units of work a traced window held.  On a TPU
each operation of the trace is named by its HLO text, so a Pallas kernel
is an operation ``%<kernel>.<n> = (<result shapes>) custom-call(<operand
shapes>), custom_call_target="tpu_custom_call"``, named after the function
that called ``pallas_call`` (``fps_blocks``, ``ball_query_blocks``, ...).
"""
from __future__ import annotations

import dataclasses
import math
import re

from bench import pointops

KERNELS = {"fps_blocks": "fps", "ball_query_blocks": "ball_query",
           "knn_blocks": "knn", "gather_blocks": "gather",
           "scatter_add_blocks": "scatter_add"}
_CALL = re.compile(r"^%?([A-Za-z_]+?)(?:\.\d+)? = ")
_SHAPE = re.compile(r"\b(?:f32|s32|pred|bf16|u32|s8|u8|f16)\[([0-9,]*)\]")


@dataclasses.dataclass
class Call:
    kind: str
    dur_ns: int
    shapes: list       # result shapes first, then operand shapes


def kind_of(op):
    if "tpu_custom_call" not in op.name:
        return None
    m = _CALL.match(op.name)
    return KERNELS.get(m.group(1)) if m else None


def _shapes(op):
    """Result shapes, then operand shapes, in the order the HLO text gives
    them (layout constraints after the operands are not read)."""
    text = op.name.split("custom_call_target=")[0]
    return [tuple(int(x) for x in s.split(",") if x)
            for s in _SHAPE.findall(text)]


def kernel_calls(red) -> list:
    out = []
    for op in red.ops:
        kind = kind_of(op)
        if kind is not None:
            out.append(Call(kind, op.dur_ns, _shapes(op)))
    return out


def dims(call: Call):
    """The dimensions ``pointops.count`` needs, from the call's shapes
    (the result's, then the operands'); leading axes fold into blocks."""
    s = call.shapes
    lead = lambda shape: math.prod(shape[:-2])  # noqa: E731
    if call.kind == "fps":
        res, coords = s[0], s[1]
        return {"nb": lead(coords), "bs": coords[-1], "k": res[-1]}
    if call.kind == "ball_query":
        idx, centers, window = s[0], s[3], s[5]
        return {"nb": lead(centers), "kc": centers[-1], "w": window[-1],
                "num": idx[-1]}
    if call.kind == "knn":
        idx, queries, window = s[0], s[2], s[3]
        return {"nb": lead(queries), "q": queries[-1], "w": window[-1],
                "k": idx[-1]}
    if call.kind == "gather":
        out, feats = s[0], s[1]
        return {"nb": lead(feats), "m": out[-2], "c": feats[-1]}
    out, g = s[0], s[1]
    return {"nb": lead(g), "m": g[-2], "c": g[-1], "w": out[-2]}


def roofline(calls, peaks):
    """(sum of least seconds, sum of kernel seconds, {bound: calls})."""
    least = spent = 0.0
    bounds = {}
    for c in calls:
        ops, nbytes = pointops.count(c.kind, dims(c))
        t, bound = pointops.least_time(ops, nbytes, peaks)
        least += t
        spent += c.dur_ns * 1e-9
        bounds[bound] = bounds.get(bound, 0) + 1
    return least, spent, bounds


def _with_kernels(red) -> set:
    """(device, start) of the module executions that ran a kernel."""
    out = set()
    for op in red.ops:
        if kind_of(op) is not None:
            out.add((op.device, op.module_start))
    return out


def plan_runs(red) -> list:
    """Executions of the partition plan executable: the serving engine's
    cached executables are jitted under one name (its ``PlanCache``), and
    of those the plan is the one that runs no point-op kernel."""
    forward = serve_runs(red)
    if not forward:
        return []
    name = _base(forward[0].name)
    hit = _with_kernels(red)
    return [m for m in red.modules if _base(m.name) == name
            and (m.device, m.start_ns) not in hit]


def _base(module: str) -> str:
    # "jit_counted(12)" -> "jit_counted"
    return module.split("(")[0]


def serve_runs(red) -> list:
    """Executions of the model's forward (or step): the modules that run
    the point-op kernels."""
    hit = _with_kernels(red)
    return [m for m in red.modules if (m.device, m.start_ns) in hit]


def units(run) -> int:
    """Units of work in the traced window: as the driver counted them, or
    else the executions of the serve forward."""
    rd = run.reading
    if rd.get("units"):
        return rd["units"]
    return len(serve_runs(rd["reduced"])) or None
