"""A configuration file, as the program under test takes it, and the
weights the benchmark makes for it.

Everything here reads ``bench/configs/<name>.json``; nothing here depends
on a cell or a traffic mix.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import pathlib

import jax

from bench import reference

CONFIGS = pathlib.Path(__file__).resolve().parent / "configs"


def load(name: str) -> dict:
    return json.loads((CONFIGS / f"{name}.json").read_text())


def pnn_config(cfg: dict, n_points: int | None = None):
    """The program's model configuration for a configuration file."""
    from repro.models import pnn
    stages = tuple(pnn.SAStage(s["rate"], s["radius"], s["nsample"],
                               tuple(s["mlp"])) for s in cfg["sa_stages"])
    return pnn.PNNConfig(
        name=cfg["name"], variant="pointnet2", task=cfg["task"],
        num_classes=cfg["num_classes"],
        n_points=n_points or cfg["num_points"],
        in_channels=cfg["in_channels"], stages=stages,
        fp_widths=tuple(tuple(f) for f in cfg["fp_mlp"]),
        head_widths=tuple(cfg["head_mlp"]), point_ops=cfg["point_ops"],
        th=cfg["th"], strategy=cfg["strategy"], impl=cfg["impl"])


def model_overrides(cfg: dict) -> dict:
    """The model fields the serving configuration accepts."""
    m = pnn_config(cfg)
    return {"variant": m.variant, "num_classes": m.num_classes, "th": m.th,
            "strategy": m.strategy, "point_ops": m.point_ops,
            "impl": m.impl, "stages": m.stages, "fp_widths": m.fp_widths}


@functools.lru_cache(maxsize=None)
def _init_fn(cfg_json: str):
    cfg = json.loads(cfg_json)
    return jax.jit(lambda key: reference.init_params(key, cfg))


def weights(cfg: dict, key):
    """The network's float32 weights, made on the device in one jitted
    call from ``key``."""
    return _init_fn(json.dumps(cfg, sort_keys=True))(key)


@dataclasses.dataclass(frozen=True)
class Flops:
    """Dense-layer FLOPs of one cloud of ``n`` real points (two per
    multiply-add; LayerNorm, pooling and the point ops are not counted)."""

    sa: tuple
    fp: tuple
    head: int

    @property
    def total(self) -> int:
        return sum(self.sa) + sum(self.fp) + self.head


def dense_flops(cfg: dict, n: int) -> Flops:
    shapes = reference.param_shapes(cfg)
    sizes = reference.stage_sizes(n, cfg["sa_stages"])
    sa = tuple(sizes[i + 1] * s["nsample"] *
               sum(2 * a * b for a, b in shapes["sa"][i])
               for i, s in enumerate(cfg["sa_stages"]))
    nst = len(cfg["sa_stages"])
    fp = tuple(sizes[nst - 1 - i] * sum(2 * a * b for a, b in dims)
               for i, dims in enumerate(shapes["fp"]))
    head = n * (sum(2 * a * b for a, b in shapes["head"])
                + 2 * shapes["out"][0] * shapes["out"][1])
    return Flops(sa, fp, head)
