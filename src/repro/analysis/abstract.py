"""Abstract interface checks: eval_shape parity + tile budgets, no kernels.

The numeric test suite proves the kernels *compute* the right values; this
layer proves the *interfaces* agree without executing anything.  Every
public op in ``kernels/ops.py`` is run under ``jax.eval_shape`` across a
shape ladder × impl matrix and checked three ways:

* **ABS001 cross-impl parity** — the pallas and xla backends (and the
  chunked vs unchunked paths) must produce identical shape/dtype trees:
  the dispatch layer's promise that ``impl=`` is a pure performance knob.
* **ABS002 oracle conformance** — the public wrapper's outputs must match
  ``kernels/ref.py`` evaluated on unpadded lane-major inputs: the
  slice-back-to-caller-shapes half of the dispatch contract (a padded
  lane leaking into a caller shape shows up here, with no kernel run).
* **ABS003/ABS004 tile discipline** — each op's declared VMEM tiles must
  divide their padded arrays exactly (BlockSpec divisibility), respect
  f32 (8, 128) tiling on the sublane/lane axes, and fit a per-kernel
  VMEM footprint budget (~16 MiB/core on v5e-class parts, with headroom
  for compiler temporaries).

Shapes in the ladder are deliberately *not* lane-aligned (33, 65, 200 …)
so the padding/slicing contract is actually exercised.
"""
from __future__ import annotations

import dataclasses
import functools
import inspect

from repro.analysis.report import ERROR, Finding

LANE = 128
SUBLANE = 8                       # f32 min tile is (8, 128)
VMEM_BYTES = 16 * 2 ** 20         # per-core VMEM, TPU v5e class
VMEM_FILL_MAX = 0.75              # headroom for compiler temporaries
F32 = 4                           # bytes

IMPLS = ("xla", "pallas")
CHUNKS = (None, 2)

# The shape ladder: (nb blocks, block size, samples k, neighbors num,
# window w, gather rows m, channels c).  Mixed lane-misaligned sizes.
MATRIX = (
    dict(nb=1, bs=33, k=8, num=8, w=200, m=40, c=3),
    dict(nb=3, bs=65, k=16, num=8, w=128, m=64, c=35),
    dict(nb=2, bs=256, k=64, num=32, w=512, m=256, c=64),
)


def _pad(n: int, m: int) -> int:
    return n + (-n) % m


@dataclasses.dataclass(frozen=True)
class Tile:
    """One VMEM-resident buffer of a kernel grid step.

    ``ref=False`` marks a traced intermediate (one-hot / distance
    matrices): it counts toward the VMEM footprint but is exempt from the
    BlockSpec divisibility/alignment checks — Mosaic relays intermediates
    itself; only actual ref tiles carry the layout contract."""

    name: str
    array: tuple       # full (padded) array shape the grid iterates over
    block: tuple       # per-step block shape
    bytes_per_elem: int = F32
    ref: bool = True

    @property
    def nbytes(self) -> int:
        n = self.bytes_per_elem
        for d in self.block:
            n *= d
        return n


@dataclasses.dataclass(frozen=True)
class OpCase:
    """One public op's abstract interface: how to call it, what the ref
    oracle says, and which VMEM tiles its pallas kernel materializes."""

    name: str
    wrapper: object                # the kernels/ops.py public function
    make_inputs: object            # dims dict -> user-layout avals
    call: object                   # (inputs, impl, chunk) -> eval_shape out
    oracle: object                 # dims dict -> ref-oracle eval_shape out
    tiles: object                  # dims dict -> list[Tile]


def _specs(tree):
    import jax

    return jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), tree)


def _loc(wrapper):
    """(path, line) of a public wrapper, repo-relative when possible."""
    path = inspect.getsourcefile(wrapper) or "<unknown>"
    for marker in ("src/repro/",):
        if marker in path:
            path = marker + path.split(marker, 1)[1]
    try:
        line = inspect.getsourcelines(wrapper)[1]
    except OSError:
        line = 1
    return path, line


def build_cases() -> tuple:
    """The op table.  Imported lazily so `python -m repro.analysis <file>`
    (pure lint) never pays the jax import."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops as kops
    from repro.kernels import ref as _ref
    from repro.kernels.common import leaves_per_step

    f32, i32 = jnp.float32, jnp.int32

    def aval(shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype)

    def lane_major(d):
        """Unpadded lane-major avals for the ref oracle (NB, 3, BS)."""
        return (aval((d["nb"], 3, d["bs"])), aval((d["nb"], 1, d["bs"])))

    def ev(fn, *args, **kw):
        return jax.eval_shape(functools.partial(fn, **kw), *args)

    def search_tiles(d, name, rows, out):
        """A neighbour search's tiles: query rows padded to 8 sublanes, G
        leaves per grid step, leaves padded to a multiple of G."""
        r = _pad(rows, SUBLANE)
        g = leaves_per_step(r, d["nb"])
        nb, w = _pad(d["nb"], g), _pad(d["w"], LANE)
        return [Tile(name, (nb, 3, r), (g, 3, r)),
                Tile("window", (nb, 3, w), (g, 3, w)),
                Tile("d2_matrix", (g * r, w), (g * r, w), ref=False),
                Tile("idx_out", (nb, r, out), (g, r, out)),
                Tile("d2_out", (nb, r, out), (g, r, out))]

    cases = []

    # fps_blocks -----------------------------------------------------------
    cases.append(OpCase(
        name="fps_blocks", wrapper=kops.fps_blocks,
        make_inputs=lambda d: (aval((d["nb"], d["bs"], 3)),
                               aval((d["nb"], d["bs"]), jnp.bool_)),
        call=lambda inp, impl, chunk, d: ev(
            kops.fps_blocks, *inp, k=d["k"], impl=impl, chunk=chunk),
        oracle=lambda d: ev(_ref.fps_blocks, *lane_major(d), k=d["k"]),
        tiles=lambda d: [
            Tile("coords", (d["nb"], 3, _pad(d["bs"], LANE)),
                 (1, 3, _pad(d["bs"], LANE))),
            Tile("vmask", (d["nb"], 1, _pad(d["bs"], LANE)),
                 (1, 1, _pad(d["bs"], LANE))),
            Tile("mind_scratch", (1, _pad(d["bs"], LANE)),
                 (1, _pad(d["bs"], LANE))),
            Tile("idx_out", (d["nb"], d["k"]), (1, d["k"])),
        ]))

    # ball_query_blocks ----------------------------------------------------
    cases.append(OpCase(
        name="ball_query_blocks", wrapper=kops.ball_query_blocks,
        make_inputs=lambda d: (aval((d["nb"], d["k"], 3)),
                               aval((d["nb"], d["k"]), jnp.bool_),
                               aval((d["nb"], d["w"], 3)),
                               aval((d["nb"], d["w"]), jnp.bool_)),
        call=lambda inp, impl, chunk, d: ev(
            kops.ball_query_blocks, *inp, radius=0.3, num=d["num"],
            impl=impl, chunk=chunk),
        oracle=lambda d: ev(
            _ref.ball_query_blocks,
            aval((d["nb"], 3, d["k"])), aval((d["nb"], 1, d["k"])),
            aval((d["nb"], 3, d["w"])), aval((d["nb"], 1, d["w"])),
            radius=0.3, num=d["num"]),
        tiles=lambda d: search_tiles(d, "centers", d["k"], d["num"])))

    # knn_blocks -----------------------------------------------------------
    cases.append(OpCase(
        name="knn_blocks", wrapper=kops.knn_blocks,
        make_inputs=lambda d: (aval((d["nb"], d["m"], 3)),
                               aval((d["nb"], d["w"], 3)),
                               aval((d["nb"], d["w"]), jnp.bool_)),
        call=lambda inp, impl, chunk, d: ev(
            kops.knn_blocks, *inp, k=3, impl=impl, chunk=chunk),
        oracle=lambda d: ev(
            _ref.knn_blocks,
            aval((d["nb"], 3, d["m"])),
            aval((d["nb"], 3, d["w"])), aval((d["nb"], 1, d["w"])), k=3),
        tiles=lambda d: search_tiles(d, "queries", d["m"], 3)))

    # gather_blocks (forward + its scatter-add backward tiles) -------------
    cases.append(OpCase(
        name="gather_blocks", wrapper=kops.gather_blocks,
        make_inputs=lambda d: (aval((d["nb"], d["w"], d["c"])),
                               aval((d["nb"], d["m"]), i32)),
        call=lambda inp, impl, chunk, d: ev(
            kops.gather_blocks, *inp, impl=impl, chunk=chunk),
        oracle=lambda d: ev(
            _ref.gather_blocks,
            aval((d["nb"], d["w"], d["c"])), aval((d["nb"], d["m"]), i32)),
        tiles=lambda d: [
            Tile("window_feats",
                 (d["nb"], _pad(d["w"], SUBLANE), _pad(d["c"], LANE)),
                 (1, _pad(d["w"], SUBLANE), _pad(d["c"], LANE))),
            Tile("onehot", (d["m"], _pad(d["w"], SUBLANE)),
                 (d["m"], _pad(d["w"], SUBLANE)), ref=False),
            Tile("out", (d["nb"], d["m"], _pad(d["c"], LANE)),
                 (1, d["m"], _pad(d["c"], LANE))),
            # backward (scatter_add_blocks): cotangents lane-padded on M,
            # window padded to the sublane multiple.
            Tile("bwd_g", (d["nb"], _pad(d["m"], LANE), _pad(d["c"], LANE)),
                 (1, _pad(d["m"], LANE), _pad(d["c"], LANE))),
            Tile("bwd_onehot_t",
                 (_pad(d["w"], SUBLANE), _pad(d["m"], LANE)),
                 (_pad(d["w"], SUBLANE), _pad(d["m"], LANE)), ref=False),
            Tile("bwd_out",
                 (d["nb"], _pad(d["w"], SUBLANE), _pad(d["c"], LANE)),
                 (1, _pad(d["w"], SUBLANE), _pad(d["c"], LANE))),
        ]))

    # fractal_level_blocks -------------------------------------------------
    cases.append(OpCase(
        name="fractal_level_blocks", wrapper=kops.fractal_level_blocks,
        make_inputs=lambda d: (aval((d["nb"], d["bs"], 3)),
                               aval((d["nb"], d["bs"]), jnp.bool_),
                               aval((d["nb"],))),
        call=lambda inp, impl, chunk, d: ev(
            kops.fractal_level_blocks, *inp, da=0, db=1, impl=impl,
            chunk=chunk),
        oracle=lambda d: ev(
            _ref.fractal_level_blocks, *lane_major(d),
            aval((d["nb"], 1)), da=0, db=1),
        tiles=lambda d: [
            Tile("coords", (d["nb"], 3, _pad(d["bs"], LANE)),
                 (1, 3, _pad(d["bs"], LANE))),
            Tile("side_out", (d["nb"], _pad(d["bs"], LANE)),
                 (1, _pad(d["bs"], LANE))),
        ]))

    return tuple(cases)


def check_case(case: OpCase, dims: dict) -> list:
    """All abstract checks for one (op, shape-row) cell."""
    path, line = _loc(case.wrapper)

    def finding(rule, msg):
        return Finding(path=path, line=line, rule=rule, severity=ERROR,
                       message=f"{case.name}{_dims_str(dims)}: {msg}")

    out = []
    inputs = case.make_inputs(dims)

    # ABS001: impl x chunk parity.
    got = {}
    for impl in IMPLS:
        for chunk in CHUNKS:
            try:
                got[(impl, chunk)] = _specs(
                    case.call(inputs, impl, chunk, dims))
            except Exception as e:  # abstract eval itself failed
                out.append(finding(
                    "ABS001", f"eval_shape failed for impl={impl} "
                    f"chunk={chunk}: {type(e).__name__}: {e}"))
    if out:
        return out
    base = got[("xla", None)]
    for key, specs in got.items():
        if specs != base:
            out.append(finding(
                "ABS001", f"impl={key[0]} chunk={key[1]} disagrees with "
                f"impl=xla chunk=None: {specs} != {base}"))

    # ABS002: conformance with the kernels/ref.py oracle.
    oracle = _specs(case.oracle(dims))
    if base != oracle:
        out.append(finding(
            "ABS002", f"public wrapper spec {base} != ref-oracle spec "
            f"{oracle} — outputs not sliced back to caller shapes?"))

    # ABS003: BlockSpec divisibility + f32 tiling alignment.
    total = 0
    for tile in case.tiles(dims):
        total += tile.nbytes
        if not tile.ref:
            continue
        for a, b in zip(tile.array, tile.block):
            if b == 0 or a % b:
                out.append(finding(
                    "ABS003", f"tile '{tile.name}': block {tile.block} "
                    f"does not divide array {tile.array}"))
                break
        if len(tile.block) >= 2 and tile.block[-1] >= LANE and \
                tile.block[-1] % LANE:
            out.append(finding(
                "ABS003", f"tile '{tile.name}': lane axis {tile.block[-1]} "
                f"is not a multiple of {LANE}"))

    # ABS004: VMEM footprint budget.
    budget = int(VMEM_BYTES * VMEM_FILL_MAX)
    if total > budget:
        out.append(finding(
            "ABS004", f"VMEM footprint {total / 2**20:.2f} MiB exceeds "
            f"budget {budget / 2**20:.2f} MiB "
            f"({VMEM_FILL_MAX:.0%} of {VMEM_BYTES / 2**20:.0f} MiB)"))
    return out


def _dims_str(dims: dict) -> str:
    return "[" + ",".join(f"{k}={v}" for k, v in sorted(dims.items())) + "]"


def run_interface_checks(matrix=None) -> list:
    """The full op x shape matrix; returns findings (empty == parity)."""
    findings = []
    for case in build_cases():
        for dims in (matrix or MATRIX):
            findings.extend(check_case(case, dims))
    return findings
