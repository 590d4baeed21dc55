"""jit'd public wrappers for the FractalCloud kernels — the *dispatch layer*.

Each op accepts ``impl``:

* ``"pallas"``    — the TPU kernel (compiled on TPU, interpreted on CPU;
                    any other backend is an error — ``interpret_kernels``);
* ``"xla"``       — the pure-jnp oracle (kernels/ref.py);
* ``None``        — resolved from ``$REPRO_POINT_IMPL`` (default ``"pallas"``
                    here at the kernel layer; ``core/bppo.py`` defaults its
                    callers to ``"xla"``).

This layer owns the whole execution contract so callers never re-implement
it ad hoc (docs/DESIGN.md §4):

* *layout* — user-facing tensors are (NB, BS, 3) / (NB, BS); kernels consume
  lane-major (NB, 3, BS') with BS' padded to the 128-lane boundary (padded
  lanes masked invalid) and results sliced back to caller shapes;
* *leaf-chunking* — every op takes ``chunk``: the block axis is processed
  ``chunk`` blocks per ``lax.map`` step, bounding the live distance /
  gather-tile footprint at large scale (``leaf_chunks`` is the shared
  pad+reshape helper).

``impl=None`` is resolved eagerly in the public wrappers, before the jitted
inner functions (whose caches key on the concrete impl) — flipping
``$REPRO_POINT_IMPL`` mid-process affects the next eager call, never a
stale jit cache.  Inside an outer jit, resolution still happens at that
trace's time.

Both impls are trainable end to end: every public wrapper carries a custom
VJP (``kernels/vjp.py``) — ``gather_blocks`` differentiates in its features
(backward = transposed one-hot scatter-add, dispatched like the forward),
and FPS / ball query / kNN / fractal-level are non-differentiable index
producers whose outputs carry zero cotangents.  One ``custom_vjp`` instance
is cached per static-arg signature, so jit caches stay keyed the same way.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import ball_query as _bq
from repro.kernels import fps as _fps
from repro.kernels import fractal_engine as _fe
from repro.kernels import gather as _ga
from repro.kernels import knn as _knn
from repro.kernels import ref as _ref
from repro.kernels import vjp as _vjp

LANE = 128
IMPLS = ("xla", "pallas")


def resolve_impl(impl: str | None = None, default: str = "pallas") -> str:
    """Resolve an impl choice: explicit arg > $REPRO_POINT_IMPL > default."""
    if impl is None:
        impl = os.environ.get("REPRO_POINT_IMPL") or default
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    return impl


def interpret_kernels() -> bool:
    """Whether Pallas kernels run in interpret mode: on the CPU backend
    (tests, small rehearsals) yes, on TPU never.  Any other backend is
    refused rather than left quietly interpreting."""
    backend = jax.default_backend()
    if backend in ("cpu", "tpu"):
        return backend == "cpu"
    raise RuntimeError(f"Pallas kernels compile for TPU and interpret on "
                       f"CPU; backend {backend!r} is neither")


def _pad_lanes(x, axis, mult=LANE, value=0):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _to_lane_major(coords, mask):
    """(NB, BS, 3), (NB, BS) -> (NB, 3, BS'), (NB, 1, BS')."""
    c = _pad_lanes(jnp.swapaxes(coords, -1, -2), -1)
    m = _pad_lanes(mask.astype(jnp.float32)[:, None, :], -1)
    return c, m


def pad_points(coords, n: int, valid=None):
    """Admission-time bucket padding: grow a ``(..., p, 3)`` cloud to exactly
    ``n`` points, marking the tail invalid.

    The serving layer's analogue of this module's lane padding (see
    docs/DESIGN.md §9): padded slots carry a ``False`` mask and are never
    observed, so every cloud admitted to a shape bucket hits the one cached
    executable compiled for that bucket.  Returns ``(coords, valid)`` with
    shapes ``(..., n, 3)`` / ``(..., n)``.
    """
    p = coords.shape[-2]
    if n < p:
        raise ValueError(f"cannot pad {p} points down to {n}")
    if valid is None:
        valid = jnp.ones(coords.shape[:-1], bool)
    pad = n - p
    if pad:
        wc = [(0, 0)] * coords.ndim
        wc[-2] = (0, pad)
        coords = jnp.pad(coords, wc)
        wv = [(0, 0)] * valid.ndim
        wv[-1] = (0, pad)
        valid = jnp.pad(valid, wv)
    return coords, valid


def leaf_chunks(arrays, chunk):
    """Pad leading (block) dims to a chunk multiple and reshape to
    (n_chunks, chunk, ...) for lax.map/scan over block chunks.  Returns
    (chunked arrays, original leading size).

    Public: callers that stream a custom carry over chunks (e.g. bppo's
    interpolation scatter-scan) build their chunk layout here so the
    pad/reshape contract lives in one place."""
    nb = arrays[0].shape[0]
    pad = (-nb) % chunk

    def prep(a):
        if pad:
            a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((nb + pad) // chunk, chunk, *a.shape[1:])

    return tuple(prep(a) for a in arrays), nb


def _chunked(fn, arrays, chunk):
    """Apply ``fn`` to ``chunk``-block slices of the leading axis via
    lax.map (padded blocks carry zero masks and are sliced off)."""
    nb = arrays[0].shape[0]
    if chunk is None or chunk >= nb:
        return fn(*arrays)
    chunks, _ = leaf_chunks(arrays, chunk)
    out = jax.lax.map(lambda xs: fn(*xs), chunks)
    return jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:])[:nb], out)


@functools.lru_cache(maxsize=None)
def _fps_op(k: int, impl: str, chunk: int | None):
    return _vjp.index_producer(
        functools.partial(_fps_blocks, k=k, impl=impl, chunk=chunk))


def fps_blocks(coords, mask, *, k: int, impl: str | None = None,
               chunk: int | None = None):
    """coords (NB, BS, 3), mask (NB, BS) -> sampled in-block idx (NB, k).

    If ``k`` exceeds a block's valid count, the exhausted slots repeat the
    last valid selection (empty blocks repeat index 0) — both impls,
    asserted in tests/test_point_impls.py."""
    return _fps_op(k, resolve_impl(impl), chunk)(coords, mask)


@functools.partial(jax.jit, static_argnames=("k", "impl", "chunk"))
def _fps_blocks(coords, mask, *, k, impl, chunk):
    def run(coords, mask):
        c, m = _to_lane_major(coords, mask)
        if impl == "pallas":
            return _fps.fps_blocks(c, m, k=k, interpret=interpret_kernels())
        return _ref.fps_blocks(c, m, k=k)

    return _chunked(run, (coords, mask), chunk)


@functools.lru_cache(maxsize=None)
def _ball_query_op(radius: float, num: int, impl: str, chunk: int | None):
    return _vjp.index_producer(
        functools.partial(_ball_query_blocks, radius=radius, num=num,
                          impl=impl, chunk=chunk))


def ball_query_blocks(centers, cmask, window, wmask, *, radius: float,
                      num: int, impl: str | None = None,
                      chunk: int | None = None):
    """centers (NB,KC,3), cmask (NB,KC), window (NB,W,3), wmask (NB,W)
    -> (idx (NB,KC,num) local-to-window, d2 (NB,KC,num), cnt (NB,KC))."""
    return _ball_query_op(radius, num, resolve_impl(impl), chunk)(
        centers, cmask, window, wmask)


@functools.partial(jax.jit,
                   static_argnames=("radius", "num", "impl", "chunk"))
def _ball_query_blocks(centers, cmask, window, wmask, *, radius, num, impl,
                       chunk):
    kc = centers.shape[1]

    def run(centers, cmask, window, wmask):
        c, cm = _to_lane_major(centers, cmask)
        w, wm = _to_lane_major(window, wmask)
        if impl == "pallas":
            idx, d2, cnt = _bq.ball_query_blocks(
                c, cm, w, wm, radius=radius, num=num,
                interpret=interpret_kernels())
        else:
            idx, d2, cnt = _ref.ball_query_blocks(c, cm, w, wm,
                                                  radius=radius, num=num)
        return idx[:, :kc], d2[:, :kc], cnt[:, :kc]

    return _chunked(run, (centers, cmask, window, wmask), chunk)


@functools.lru_cache(maxsize=None)
def _knn_op(k: int, impl: str, chunk: int | None):
    return _vjp.index_producer(
        functools.partial(_knn_blocks, k=k, impl=impl, chunk=chunk))


def knn_blocks(queries, window, wmask, *, k: int, impl: str | None = None,
               chunk: int | None = None):
    """queries (NB,Q,3), window (NB,W,3), wmask (NB,W)
    -> (idx (NB,Q,k) local-to-window, d2 (NB,Q,k))."""
    return _knn_op(k, resolve_impl(impl), chunk)(queries, window, wmask)


@functools.partial(jax.jit, static_argnames=("k", "impl", "chunk"))
def _knn_blocks(queries, window, wmask, *, k, impl, chunk):
    nq = queries.shape[1]

    def run(queries, window, wmask):
        q, _ = _to_lane_major(queries, jnp.ones(queries.shape[:2], bool))
        w, wm = _to_lane_major(window, wmask)
        if impl == "pallas":
            idx, d2 = _knn.knn_blocks(q, w, wm, k=k,
                                      interpret=interpret_kernels())
        else:
            idx, d2 = _ref.knn_blocks(q, w, wm, k=k)
        return idx[:, :nq], d2[:, :nq]

    return _chunked(run, (queries, window, wmask), chunk)


@functools.lru_cache(maxsize=None)
def _gather_op(w: int, impl: str, chunk: int | None):
    return _vjp.gathering(
        functools.partial(_gather_blocks, impl=impl, chunk=chunk),
        functools.partial(_gather_grad_blocks, w=w, impl=impl, chunk=chunk))


def gather_blocks(window_feats, idx, *, impl: str | None = None,
                  chunk: int | None = None):
    """window_feats (NB, W, C), idx (NB, M) local-to-window -> (NB, M, C).

    Out-of-range idx (negative or >= W) fetches zeros, both impls — the
    masked-invalid contract the backward mirrors by dropping those rows.
    Differentiable in ``window_feats`` (kernels/vjp.py)."""
    return _gather_op(window_feats.shape[-2], resolve_impl(impl), chunk)(
        window_feats, idx)


@functools.partial(jax.jit, static_argnames=("impl", "chunk"))
def _gather_blocks(window_feats, idx, *, impl, chunk):
    c_out = window_feats.shape[-1]

    def run(window_feats, idx):
        if impl == "pallas":
            f = _pad_lanes(window_feats, -1)          # C on lanes
            f = _pad_lanes(f, -2, mult=8)             # W on sublanes
            out = _ga.gather_blocks(f, idx, interpret=interpret_kernels())
            return out[..., :c_out]
        return _ref.gather_blocks(window_feats, idx)

    return _chunked(run, (window_feats, idx), chunk)


@functools.partial(jax.jit, static_argnames=("w", "impl", "chunk"))
def _gather_grad_blocks(g, idx, *, w, impl, chunk):
    """gather_blocks' backward dispatch: cotangent rows g (NB, M, C)
    scatter-added at idx into (NB, W, C) window cotangents."""
    c_out = g.shape[-1]

    def run(g, idx):
        if impl == "pallas":
            gg = _pad_lanes(g, -1)                    # C on lanes
            gg = _pad_lanes(gg, -2)                   # M: contraction dim,
            ii = _pad_lanes(idx, -1, value=-1)        # padded rows dropped
            out = _ga.scatter_add_blocks(gg, ii, w=w + (-w) % 8,
                                         interpret=interpret_kernels())
            return out[:, :w, :c_out]
        return _ref.scatter_add_blocks(g, idx, w=w)

    return _chunked(run, (g, idx), chunk)


@functools.lru_cache(maxsize=None)
def _fractal_level_op(da: int, db: int, impl: str, chunk: int | None):
    return _vjp.index_producer(
        functools.partial(_fractal_level_blocks, da=da, db=db, impl=impl,
                          chunk=chunk))


def fractal_level_blocks(coords, mask, mid, *, da: int, db: int,
                         impl: str | None = None, chunk: int | None = None):
    """coords (NB,BS,3), mask (NB,BS), mid (NB,) ->
    (side (NB,BS) i32, left_count (NB,), child_stats (NB,4))."""
    return _fractal_level_op(da, db, resolve_impl(impl), chunk)(
        coords, mask, mid)


@functools.partial(jax.jit, static_argnames=("da", "db", "impl", "chunk"))
def _fractal_level_blocks(coords, mask, mid, *, da, db, impl, chunk):
    bs = coords.shape[1]

    def run(coords, mask, mid):
        c, m = _to_lane_major(coords, mask)
        if impl == "pallas":
            side, lcnt, stats = _fe.fractal_level_blocks(
                c, m, mid[:, None], da=da, db=db,
                interpret=interpret_kernels())
        else:
            side, lcnt, stats = _ref.fractal_level_blocks(
                c, m, mid[:, None], da=da, db=db)
        return side[:, :bs], lcnt, stats

    return _chunked(run, (coords, mask, mid), chunk)
