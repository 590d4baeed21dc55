"""Operations and bytes that each point-op kernel call needs, from its
shapes, whatever implements it.

Shapes are those of one call as the kernel receives it (lane-major
coordinates, point axes padded to 128 lanes); the counts cover the work
the operation needs on those blocks:

* ``fps``: k rounds over BS points, each a squared distance to the last
  pick (3 sub, 3 mul, 2 add), a running minimum and a compare for the
  arg-max: 10 operations per point and round.  Bytes: coordinates and mask
  in, k indices out.
* ``ball_query``: a squared distance per (centre, window point) pair (8),
  the radius test (1) and one step of selecting the nearest in-radius
  points (1).  Bytes: centres, window, masks in; indices, distances and
  counts out.
* ``knn``: a squared distance per (query, window point) pair (8) and k
  compares to keep the k nearest.  Bytes: queries, window, mask in;
  indices and distances out.
* ``gather``: no arithmetic; the rows fetched, the indices and the rows
  written (not the one-hot matmul a kernel may use to fetch them).
* ``scatter_add``: one add per cotangent element; cotangents and indices
  in, the window's rows out.

The least time of a call is the larger of its operations over the chip's
peak FLOP/s and its bytes over the peak memory bandwidth.
"""
from __future__ import annotations

F32 = 4


def count(kind: str, shapes: dict) -> tuple[float, float]:
    """(operations, bytes) of one call; ``shapes`` names its dimensions."""
    s = shapes
    if kind == "fps":
        nb, bs, k = s["nb"], s["bs"], s["k"]
        return 10.0 * nb * k * bs, F32 * nb * (4 * bs + k)
    if kind == "ball_query":
        nb, kc, w, num = s["nb"], s["kc"], s["w"], s["num"]
        return (10.0 * nb * kc * w,
                F32 * nb * (4 * kc + 4 * w + 2 * kc * num + kc))
    if kind == "knn":
        nb, q, w, k = s["nb"], s["q"], s["w"], s["k"]
        return (float(8 + k) * nb * q * w,
                F32 * nb * (3 * q + 4 * w + 2 * q * k))
    if kind == "gather":
        nb, m, c = s["nb"], s["m"], s["c"]
        return 0.0, F32 * nb * (2 * m * c + m)
    if kind == "scatter_add":
        nb, m, c, w = s["nb"], s["m"], s["c"], s["w"]
        return float(nb * m * c), F32 * nb * (m * c + m + w * c)
    raise ValueError(f"unknown point-op kernel {kind!r}")


def least_time(ops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """Least seconds a call can take on the chip, and which bound sets it."""
    t_c = ops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
