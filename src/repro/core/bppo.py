"""Block-Parallel Point Operations (BPPO) — paper §IV-B.

Every global point op is localized to the Fractal block structure:

* block-wise FPS       — FPS runs independently per leaf with one *fixed
                         sampling rate* (no per-block hyper-parameters);
* block-wise ball query / 3-NN interpolation — the search space of a center
                         in a leaf is the leaf's *immediate parent* range
                         (depth<=1: the leaf itself), a contiguous window in
                         the DFT layout;
* block-wise gathering — feature fetches confined to the same windows.

All ops work in the *permuted (DFT) frame*: indices index the sorted arrays
(``part.coords``); map back with ``part.perm[idx]``.  Everything is
static-shape and vmap/pjit-friendly; leaves are the unit of parallelism —
the same axis the launcher shards across chips.

Each op is split into a *plan* phase (window/quota/compaction index math,
pure jnp here) and an *execute* phase (the distance / argmax / top-k inner
loops), which dispatches through ``kernels/ops.py``: ``impl="xla"`` runs the
jnp oracle (kernels/ref.py), ``impl="pallas"`` the TPU kernels
(interpreted on the CPU).  ``impl=None`` resolves from
``$REPRO_POINT_IMPL`` (default ``"xla"``).  Both backends are trainable:
the execute ops carry custom VJPs (kernels/vjp.py) — gather differentiates
in its features, the index producers stop gradients — so ``jax.grad``
through any bppo op is valid at either impl.  See docs/DESIGN.md §4.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.fractal import FractalPartition, leaf_from, leaf_view, \
    subtree_slot_range, window_from
from repro.dist.logical import lc
from repro.kernels import ops as kops

Array = jax.Array
_INF = jnp.float32(3.0e38)


def _resolve(impl):
    # bppo ops default to the jnp path (differentiable, fast on CPU); the
    # kernel layer's own default stays "pallas".
    return kops.resolve_impl(impl, default="xla")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BWSamples:
    """Result of block-wise FPS (both per-leaf and compacted views)."""

    # Per-leaf (uncompacted) view; kbm = max samples per leaf.
    local_idx: Array   # (ML, kbm) int32 in-block index of each sample
    block_mask: Array  # (ML, kbm) bool  sample slot j < quota[i]
    gidx: Array        # (ML, kbm) int32 index into the sorted arrays
    quota: Array       # (ML,) int32 round(rate * leaf_vsize)
    cum_quota: Array   # (ML+1,) int32 exclusive prefix of quota
    # Compacted view (k_out static slots).
    idx: Array         # (k_out,) int32 into sorted arrays
    valid: Array       # (k_out,) bool
    coords: Array      # (k_out, 3)
    leaf: Array        # (k_out,) int32 leaf id of each sample
    total: Array       # () int32 sum of quotas (may exceed k_out; truncated)

    @property
    def k_out(self) -> int:
        return self.idx.shape[0]


def blockwise_fps(part: FractalPartition, *, rate: float, k_out: int,
                  bs: int, kbm: int | None = None,
                  impl: str | None = None) -> BWSamples:
    """Block-wise sampling (paper BWS): fixed-rate FPS per leaf, aggregated.

    Plan: leaf views + quotas + leaf-major compaction.  Execute: the masked
    FPS loop itself (the paper's RSPU sampling mode; the window-check skip
    becomes masking, visited points pinned to -inf — docs/DESIGN.md §2) runs per
    leaf via ``kernels.ops.fps_blocks``.
    """
    impl = _resolve(impl)
    if kbm is None:
        kbm = max(1, int(round(rate * bs)) + 1)
    kbm = min(kbm, bs)
    pts, mask, _ = leaf_view(part, part.coords, bs)        # (ML, bs, 3)
    pts = lc(pts, "blocks", None, None)                    # leaves -> chips
    mask = lc(mask, "blocks", None)
    quota = jnp.round(rate * part.leaf_vsize).astype(jnp.int32)
    quota = jnp.where(part.is_leaf, jnp.minimum(quota, kbm), 0)

    local = kops.fps_blocks(pts, mask, k=kbm, impl=impl)
    j = jnp.arange(kbm, dtype=jnp.int32)[None, :]
    bmask = (j < quota[:, None])
    gidx = jnp.clip(part.leaf_start[:, None] + local, 0, part.n - 1)

    cum = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(quota)])
    pos = jnp.where(bmask, cum[:-1, None] + j, k_out)      # k_out => dropped
    total = cum[-1]

    ml = quota.shape[0]
    leaf_ids = jnp.broadcast_to(jnp.arange(ml, dtype=jnp.int32)[:, None],
                                (ml, kbm))
    flat_pos = pos.reshape(-1)
    idx_c = jnp.zeros((k_out,), jnp.int32).at[flat_pos].set(
        gidx.reshape(-1), mode="drop")
    leaf_c = jnp.zeros((k_out,), jnp.int32).at[flat_pos].set(
        leaf_ids.reshape(-1), mode="drop")
    valid_c = jnp.arange(k_out) < jnp.minimum(total, k_out)
    coords_c = part.coords[idx_c] * valid_c[:, None]
    return BWSamples(local_idx=local, block_mask=bmask, gidx=gidx,
                     quota=quota, cum_quota=cum, idx=idx_c, valid=valid_c,
                     coords=coords_c, leaf=leaf_c, total=total)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BWNeighbors:
    """Block-wise neighbor-search result, aligned with BWSamples compaction."""

    idx: Array    # (k_out, num) int32 into sorted arrays
    mask: Array   # (k_out, num) bool in-radius (ball query) / valid (knn)
    cnt: Array    # (k_out,) int32 true neighbor count
    d2: Array     # (k_out, num) squared distances


def _window_to_global(widx: Array, lidx: Array) -> Array:
    """Map local-to-window neighbor indices to sorted-array indices."""
    return jnp.take_along_axis(
        jnp.broadcast_to(widx[:, None, :], lidx.shape[:2] + widx.shape[1:]),
        lidx, axis=-1)


def _neighbor_slices(part: FractalPartition, samp: BWSamples):
    """Per-leaf slice arrays the neighbor plans chunk over."""
    return (part.leaf_start, part.leaf_vsize, part.parent_start,
            part.parent_vsize, part.is_leaf,
            samp.gidx, samp.block_mask)


def _chunked_slices(sl, slice_fn, chunk):
    """Run a per-leaf-slice plan+execute body, whole or chunk at a time.

    With ``chunk``, windows are *built inside* each lax.map step, so the
    live footprint is one chunk's (chunk, w, 3) window tiles plus the
    kernel's (chunk, kbm, w) distance tile — not the full-ML plan tensors.
    Padded slice rows carry zeroed starts/masks and are sliced off."""
    if chunk is None:
        return slice_fn(sl)
    chunks, ml = kops.leaf_chunks(sl, chunk)
    out = jax.lax.map(slice_fn, chunks)
    return jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:])[:ml], out)


def _bq_slice(part, sl, *, r2, radius, num, w, impl):
    ls, lv, ps, pv, il, gidx, bmask = sl
    win, wmask, widx = window_from(ls, lv, ps, pv, il, part.coords,
                                   part.valid, w)
    win = lc(win, "blocks", None, None)
    centers = lc(part.coords[gidx], "blocks", None, None)
    lidx, nd2, cnt = kops.ball_query_blocks(centers, bmask, win, wmask,
                                            radius=radius, num=num,
                                            impl=impl)
    nd2 = jnp.maximum(nd2, 0.0)  # expanded-form sqdist can cancel below 0
    in_r = (nd2 <= r2) & bmask[..., None]
    # Pad empty slots with the nearest neighbor (ref.py convention).
    lidx = jnp.where(in_r, lidx, lidx[..., :1])
    return _window_to_global(widx, lidx), in_r, cnt, nd2


def blockwise_ball_query(part: FractalPartition, samp: BWSamples, *,
                         radius: float, num: int, w: int,
                         chunk: int | None = None,
                         impl: str | None = None) -> BWNeighbors:
    """Block-wise grouping (paper BWG): centers search their parent window.

    Plan: window/center tiles + index translation + compaction.  Execute:
    distance matrix + in-radius top-k via ``kernels.ops.ball_query_blocks``.
    ``chunk`` processes that many leaves per lax.map step — window tiles
    and the (chunk, kbm, w) distance tile replace the full-ML tensors."""
    impl = _resolve(impl)
    r2 = jnp.float32(radius) ** 2
    out = _chunked_slices(
        _neighbor_slices(part, samp),
        lambda s: _bq_slice(part, s, r2=r2, radius=radius, num=num, w=w,
                            impl=impl), chunk)
    g, in_r, cnt, nd2 = out
    return _compact_neighbors(samp, g, in_r, cnt, nd2, num)


def _knn_slice(part, sl, *, k, w, impl):
    ls, lv, ps, pv, il, gidx, bmask = sl
    win, wmask, widx = window_from(ls, lv, ps, pv, il, part.coords,
                                   part.valid, w)
    win = lc(win, "blocks", None, None)
    centers = lc(part.coords[gidx], "blocks", None, None)
    lidx, nd2 = kops.knn_blocks(centers, win, wmask, k=k, impl=impl)
    ok = (nd2 < _INF) & bmask[..., None]
    nd2 = jnp.maximum(nd2, 0.0)
    cnt = jnp.sum(ok, axis=-1).astype(jnp.int32)
    return _window_to_global(widx, lidx), ok, cnt, nd2


def blockwise_knn(part: FractalPartition, samp: BWSamples, *, k: int,
                  w: int, chunk: int | None = None,
                  impl: str | None = None) -> BWNeighbors:
    """Block-wise kNN of sampled centers inside their parent window."""
    impl = _resolve(impl)
    out = _chunked_slices(
        _neighbor_slices(part, samp),
        lambda s: _knn_slice(part, s, k=k, w=w, impl=impl), chunk)
    g, ok, cnt, nd2 = out
    return _compact_neighbors(samp, g, ok, cnt, nd2, k)


def _compact_neighbors(samp: BWSamples, gidx, mask, cnt, d2, num):
    k_out = samp.k_out
    j = jnp.arange(samp.block_mask.shape[1], dtype=jnp.int32)[None, :]
    pos = jnp.where(samp.block_mask, samp.cum_quota[:-1, None] + j, k_out)
    flat = pos.reshape(-1)
    out_i = jnp.zeros((k_out, num), jnp.int32).at[flat].set(
        gidx.reshape(-1, num), mode="drop")
    out_m = jnp.zeros((k_out, num), bool).at[flat].set(
        mask.reshape(-1, num), mode="drop")
    out_c = jnp.zeros((k_out,), jnp.int32).at[flat].set(
        cnt.reshape(-1), mode="drop")
    out_d = jnp.full((k_out, num), _INF).at[flat].set(
        d2.reshape(-1, num), mode="drop")
    return BWNeighbors(idx=out_i, mask=out_m, cnt=out_c, d2=out_d)


def coarse_window_ranges(part: FractalPartition, samp: BWSamples):
    """Per-leaf range [ca, cb) of *coarse samples* in the parent subtree.

    Sampled points inherit the DFT order (compaction is leaf-major), so the
    samples of any subtree form a contiguous range of the compacted sample
    array — the paper's contiguity argument, one level up.
    """
    L = part.leaf_of_slot.shape[0]
    total_depth = max(L.bit_length() - 1, 0)
    slo, shi = subtree_slot_range(part, part.leaf_depth, part.slot_of_leaf,
                                  total_depth)
    slo = jnp.clip(slo, 0, L)
    shi = jnp.clip(shi, 0, L)
    la = part.slot_cum_leaves[slo]
    lb = part.slot_cum_leaves[shi]
    ca = samp.cum_quota[la]
    cb = samp.cum_quota[lb]
    return ca, cb


def _interp_slice(part, samp, feats, sl, *, wc, bs, eps, impl):
    """One leaf-slice of block-wise interpolation; returns scatter payload.

    Plan: coarse candidate windows (contiguous ranges of the compacted
    sample array) + IDW weights.  Execute: the 3-NN select runs through the
    kNN kernel and the feature fetch through the in-window gather kernel.
    """
    n = part.n
    lo, cb, il, ls, lv = sl
    j = jnp.arange(wc, dtype=jnp.int32)
    cidx = lo[:, None] + j[None, :]                       # (c, wc)
    cmask = (cidx < cb[:, None]) & il[:, None]
    cidx = jnp.clip(cidx, 0, samp.k_out - 1)
    cmask = cmask & samp.valid[cidx]
    cpts = lc(samp.coords[cidx], "blocks", None, None)    # (c, wc, 3)

    fine, fmask, fidx = leaf_from(ls, lv, il, part.coords, bs)
    fine = lc(fine, "blocks", None, None)
    nidx, nd2 = kops.knn_blocks(fine, cpts, cmask, k=3, impl=impl)
    nd2 = jnp.maximum(nd2, 0.0)
    ok = nd2 < _INF
    wgt = jnp.where(ok, 1.0 / (nd2 + eps), 0.0)
    wsum = jnp.sum(wgt, axis=-1, keepdims=True)
    wgt = jnp.where(wsum > 0, wgt / jnp.maximum(wsum, eps), 0.0)
    samp_idx = jnp.take_along_axis(
        jnp.broadcast_to(cidx[:, None, :], nidx.shape[:2] + cidx.shape[1:]),
        nidx, axis=-1)                                    # into compacted samp
    c = cidx.shape[0]
    vals = kops.gather_blocks(feats[cidx], nidx.reshape(c, -1), impl=impl)
    vals = vals.reshape(c, bs, 3, feats.shape[-1])        # (c, bs, 3, C)
    blended = jnp.sum(vals * wgt[..., None], axis=-2)     # (c, bs, C)
    flat_pos = jnp.where(fmask, fidx, n).reshape(-1)
    return flat_pos, blended, samp_idx, wgt


def blockwise_interpolate(part: FractalPartition, samp: BWSamples,
                          feats: Array, *, wc: int, bs: int,
                          eps: float = 1e-8, chunk: int | None = None,
                          impl: str | None = None):
    """Block-wise interpolation (paper BWI): 3-NN IDW feature propagation
    from the sampled (coarse) cloud back to every point, with the candidate
    set restricted to coarse samples of the leaf's parent subtree.

    ``feats`` are features of the compacted samples (k_out, C).
    Returns (out (n, C) in sorted order, idx3 (n,3), w3 (n,3)).
    ``chunk`` scans over leaf chunks, scattering into the output carry (the
    live footprint is one chunk's distance/feature tiles).
    """
    impl = _resolve(impl)
    n, ml = part.n, part.ml
    c_feats = feats.shape[-1]
    ca, cb = coarse_window_ranges(part, samp)
    own = samp.cum_quota[jnp.arange(ml)]
    lo = jnp.clip(own - jnp.maximum(0, (wc - samp.quota) // 2),
                  ca, jnp.maximum(ca, cb - wc))
    sl = (lo, cb, part.is_leaf, part.leaf_start, part.leaf_vsize)

    out = jnp.zeros((n, c_feats), feats.dtype)
    idx3 = jnp.zeros((n, 3), jnp.int32)
    w3 = jnp.zeros((n, 3), jnp.float32)

    def scatter(carry, payload):
        out, idx3, w3 = carry
        flat_pos, blended, samp_idx, wgt = payload
        out = lc(out.at[flat_pos].set(
            blended.reshape(-1, c_feats), mode="drop"), "points", None)
        idx3 = idx3.at[flat_pos].set(samp_idx.reshape(-1, 3), mode="drop")
        w3 = w3.at[flat_pos].set(
            wgt.astype(jnp.float32).reshape(-1, 3), mode="drop")
        return out, idx3, w3

    if chunk is None:
        payload = _interp_slice(part, samp, feats, sl, wc=wc, bs=bs,
                                eps=eps, impl=impl)
        out, idx3, w3 = scatter((out, idx3, w3), payload)
    else:
        chunks, _ = kops.leaf_chunks(sl, chunk)

        def body(carry, s):
            payload = _interp_slice(part, samp, feats, s, wc=wc, bs=bs,
                                    eps=eps, impl=impl)
            return scatter(carry, payload), None

        (out, idx3, w3), _ = jax.lax.scan(body, (out, idx3, w3), chunks)
    return out, idx3, w3


def gather(feats: Array, idx: Array) -> Array:
    """Block-wise gathering (paper BWGa). Functionally a take over the
    *compacted* index frame; the in-window Pallas gather kernel
    (``kernels.ops.gather_blocks``) is dispatched where the window structure
    still exists — inside ``blockwise_interpolate`` — because each of its
    ``idx`` rows only touches one VMEM-resident parent window."""
    return feats[idx]
