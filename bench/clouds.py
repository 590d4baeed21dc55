"""Seeded indoor point clouds: the benchmark's one generator of inputs.

A cloud is a floor plus posed objects (sphere, box, torus, cylinder, plane,
helix surfaces with sensor noise) over a rectangular footprint, scaled so
that its x-y extent is exactly the footprint, as S3DIS blocks and ScanNet
chunks are cut.  Every number comes from a numpy generator seeded with the
run's seed and the cloud's index, so a seed gives the same clouds on any
machine.  The parameters come from a traffic file's ``clouds`` entry.
"""
from __future__ import annotations

import numpy as np

SHAPES = 6           # label of each object's points; the floor is SHAPES


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream...), for any seed size."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def _surface(shape, u, v, w):
    """Points on one unit surface, from three uniform numbers each."""
    tau = 2 * np.pi
    if shape == 0:                                    # sphere
        th, ph = tau * u, np.arccos(np.clip(2 * v - 1, -1, 1))
        return np.stack([np.sin(ph) * np.cos(th), np.sin(ph) * np.sin(th),
                         np.cos(ph)], -1)
    if shape == 1:                                    # box faces
        face = np.minimum((w * 6).astype(np.int64), 5)
        a, b, one = 2 * u - 1, 2 * v - 1, np.ones_like(u)
        faces = np.stack([np.stack(f, -1) for f in (
            (a, b, one), (a, b, -one), (a, one, b), (a, -one, b),
            (one, a, b), (-one, a, b))])
        return faces[face, np.arange(len(u))]
    if shape == 2:                                    # torus
        th, ph = tau * u, tau * v
        r = 1.0 + 0.3 * np.cos(ph)
        return np.stack([r * np.cos(th), r * np.sin(th), 0.3 * np.sin(ph)],
                        -1)
    if shape == 3:                                    # cylinder
        th = tau * u
        return np.stack([np.cos(th), np.sin(th), 2 * v - 1], -1)
    if shape == 4:                                    # plane
        return np.stack([2 * u - 1, 2 * v - 1, np.zeros_like(u)], -1)
    t = 2 * tau * u                                   # helix
    return np.stack([np.cos(t) * (1 + 0.1 * v), np.sin(t) * (1 + 0.1 * v),
                     t / tau - 1], -1)


def indoor(rng: np.random.Generator, n: int, *, footprint_m: float,
           height_m: float, objects: int, object_size_m, noise_m: float,
           floor_share: float):
    """One cloud: points (n, 3) float32 and labels (n,) int32.

    ``floor_share`` of the points lie on the floor (z = 0); the rest are
    split evenly over ``objects`` objects whose half-sizes are drawn from
    ``object_size_m`` and whose centres lie over the footprint."""
    n_floor = int(round(floor_share * n))
    per = np.full(objects, (n - n_floor) // objects)
    per[: (n - n_floor) % objects] += 1
    pts = np.empty((n, 3), np.float64)
    labels = np.empty((n,), np.int32)
    uv = rng.random((n_floor, 2))
    pts[:n_floor] = np.stack([uv[:, 0] * footprint_m, uv[:, 1] * footprint_m,
                              np.zeros(n_floor)], -1)
    labels[:n_floor] = SHAPES
    shapes = rng.integers(0, SHAPES, objects)
    lo, hi = object_size_m
    size = rng.uniform(lo, hi, (objects, 3))
    ang = rng.uniform(0, 2 * np.pi, objects)
    centre = np.stack([rng.uniform(0, footprint_m, objects),
                       rng.uniform(0, footprint_m, objects),
                       np.zeros(objects)], -1)
    centre[:, 2] = np.minimum(size[:, 2] + rng.uniform(0, height_m, objects),
                              height_m)
    pos = n_floor
    for o in range(objects):
        m = int(per[o])
        uvw = rng.random((m, 3))
        p = _surface(int(shapes[o]), uvw[:, 0], uvw[:, 1], uvw[:, 2])
        c, s = np.cos(ang[o]), np.sin(ang[o])
        rot = np.array([[c, -s, 0.0], [s, c, 0.0], [0.0, 0.0, 1.0]])
        pts[pos:pos + m] = (p * size[o]) @ rot.T + centre[o]
        labels[pos:pos + m] = shapes[o]
        pos += m
    pts += noise_m * rng.standard_normal(pts.shape)
    lo_xy = pts[:, :2].min(0)
    span = np.maximum(pts[:, :2].max(0) - lo_xy, 1e-6)
    pts[:, :2] = (pts[:, :2] - lo_xy) * (footprint_m / span)
    pts[:, 2] -= pts[:, 2].min()
    order = rng.permutation(n)
    return pts[order].astype(np.float32), labels[order]


def pool(seed: int, spec: dict, stream: int = 0):
    """``spec["pool"]`` clouds of ``spec["points"]`` points each, from a
    traffic file's ``clouds`` entry: (points (P, n, 3), labels (P, n))."""
    clouds = [indoor(rng_for(seed, stream, i), spec["points"],
                     footprint_m=spec["footprint_m"],
                     height_m=spec["height_m"], objects=spec["objects"],
                     object_size_m=spec["object_size_m"],
                     noise_m=spec["noise_m"],
                     floor_share=spec["floor_share"])
              for i in range(spec["pool"])]
    return (np.stack([c[0] for c in clouds]),
            np.stack([c[1] for c in clouds]))
