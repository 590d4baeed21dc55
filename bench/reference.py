"""Plain reference of the served network: PointNet++ SSG segmentation with
fractal partitioning and block-parallel point operations.

It is written from the published description of the two methods and does
not import the system under test.  Two parts:

* ``plan_cloud`` (numpy, float32): everything that depends on coordinates
  only -- the fractal partition (midpoint splits on the dimension cycle,
  points kept contiguous per node in depth-first order), block-wise
  farthest point sampling at a fixed rate per leaf, ball query inside each
  leaf's parent window, and 3-NN inverse-distance interpolation over the
  samples of the parent subtree.  Squared distances are summed as
  ``(dx^2 + dy^2) + dz^2`` and ties go to the lowest index.
* ``forward`` (jax.numpy, float32 at HIGHEST matmul precision): the dense
  layers (dense -> LayerNorm -> ReLU), max pooling over each group, the
  propagation MLPs and the head, driven by a plan.  ``quant="fp8"``
  computes every dense layer from float8 (e4m3) operands instead; that is
  the control that the correctness limits have to reject.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

NEG = np.float32(-3.0e38)
INF = np.float32(3.0e38)
EPS_IDW = np.float32(1e-8)


# ---------------------------------------------------------------------------
# Static sizes (the same formulas the network is specified with).
# ---------------------------------------------------------------------------

def tree_depth(n: int, th: int, slack: int = 9, cap: int = 18) -> int:
    """Static depth of the partition tree: ceil(log2(n / th)) plus slack
    levels for clustered data, capped."""
    base = max(0, math.ceil(math.log2(max(1, n) / th))) if n > th else 0
    return min(base + (slack if base > 0 else 0), cap)


def leaf_slots(n: int, th: int, depth: int) -> int:
    """Static number of leaf slots: internal nodes of one level are
    disjoint and each holds more than th points."""
    per_level = n // (th + 1)
    total = sum(min(2 ** lvl, per_level) for lvl in range(depth))
    return int(min(2 ** depth, total + 1))


def stage_sizes(n: int, stages) -> list:
    sizes = [n]
    for s in stages:
        sizes.append(max(1, int(round(sizes[-1] * s["rate"]))))
    return sizes


def _sqdist(a, b):
    """a (..., R, 3), b (..., W, 3) -> (..., R, W) float32."""
    def sq(k):
        d = a[..., :, None, k] - b[..., None, :, k]
        return d * d
    out = sq(0)
    out += sq(1)
    out += sq(2)
    return out


def _topk_min(d, k):
    """The k smallest entries of each row in ascending order, ties to the
    lower lane: the picks of k rounds of argmin, each pick masked to INF
    afterwards -- so once a row's finite entries are used up, every
    further pick is lane 0."""
    w = d.shape[-1]
    if w >= 1 << 16:
        raise ValueError(f"rows of {w} lanes exceed the 16-bit lane key")
    # Non-negative float32 bits order as the floats do; the lane breaks ties.
    key = (d.view(np.int32).astype(np.int64) << 16) | np.arange(w)
    if k < w:
        key = np.take_along_axis(key, np.argpartition(key, k - 1, -1)[:, :k],
                                 -1)
    key = np.sort(key, -1)[:, :k]
    idx = (key & 0xFFFF).astype(np.int32)
    val = np.take_along_axis(d, idx, -1)
    idx = np.where(val >= INF, 0, idx)
    return idx, val


# ---------------------------------------------------------------------------
# Fractal partition.
# ---------------------------------------------------------------------------

def partition(coords, valid, th: int, dim0: int = 0) -> dict:
    """Partition an (n, 3) cloud into <= th-point leaves in DFT order.

    A node at level l that holds more than th valid points (and l is above
    the depth cap) splits on dimension (l + dim0) % 3 at the midpoint of its
    valid points' extent: valid points with x <= mid go left, the rest of
    the valid points go right, invalid points follow the right child.  A
    leaf keeps its valid points first.  Leaves at depth <= 1 search
    themselves; deeper leaves search their parent's range."""
    coords = np.asarray(coords, np.float32)
    valid = np.asarray(valid, bool)
    n = coords.shape[0]
    depth = tree_depth(n, th)
    ml = leaf_slots(n, th, depth)
    order, leaves = [], []

    def visit(idx, lvl, node, start, parent):
        v = valid[idx]
        vs = int(v.sum())
        me = (start, len(idx), vs)
        if lvl < depth and vs > th:
            x = coords[idx, (lvl + dim0) % 3]
            xv = x[v]
            mid = (xv.min() + xv.max()) * np.float32(0.5)
            side = x > mid
            left = idx[v & ~side]
            right = np.concatenate([idx[v & side], idx[~v]])
            visit(left, lvl + 1, 2 * node, start, me)
            visit(right, lvl + 1, 2 * node + 1, start + len(left), me)
            return
        if depth > 0:
            idx = np.concatenate([idx[v], idx[~v]])
        order.append(idx)
        search = me if lvl <= 1 else parent
        leaves.append((start, len(idx), vs, lvl, node << (depth - lvl))
                      + search)

    visit(np.arange(n), 0, 0, 0, (0, n, int(valid.sum())))
    perm = np.concatenate(order).astype(np.int32)
    rec = np.zeros((ml, 8), np.int64)
    num = min(len(leaves), ml)
    rec[:num] = np.asarray(leaves[:num], np.int64)
    slot = np.where(np.arange(ml) < num, rec[:, 4], -1)
    return {
        "n": n, "depth": depth, "perm": perm, "coords": coords[perm],
        "valid": valid[perm], "is_leaf": np.arange(ml) < num,
        "start": rec[:, 0], "rsize": rec[:, 1], "vsize": rec[:, 2],
        "level": rec[:, 3], "slot": slot, "pstart": rec[:, 5],
        "pvsize": rec[:, 7], "leaf_slots_sorted": np.sort(rec[:num, 4]),
        "overflow": bool(np.any(rec[:num, 2] > th)),
    }


def _leaf_rows(start, vsize, is_leaf, n, bs):
    j = np.arange(bs)
    idx = start[:, None] + j[None, :]
    mask = is_leaf[:, None] & (j[None, :] < vsize[:, None])
    return np.clip(idx, 0, n - 1), mask


def _window_rows(part, w):
    """Each leaf's search window: w consecutive sorted positions centred on
    the leaf and clamped inside its search range's valid prefix."""
    ls, lv, ps, pv = part["start"], part["vsize"], part["pstart"], \
        part["pvsize"]
    n = part["n"]
    want = ls - np.maximum(0, (w - lv) // 2)
    lo = np.minimum(np.maximum(want, ps), np.maximum(ps, ps + pv - w))
    idx = lo[:, None] + np.arange(w)[None, :]
    mask = part["is_leaf"][:, None] & (idx < (ps + pv)[:, None])
    idx = np.clip(idx, 0, n - 1)
    return idx, mask & part["valid"][idx]


# ---------------------------------------------------------------------------
# Block-wise point operations.
# ---------------------------------------------------------------------------

def fps_leaves(pts, mask, k):
    """Farthest point sampling in every leaf at once: pts (L, bs, 3),
    mask (L, bs) -> (L, k) in-leaf indices.  The first valid point starts;
    once every valid point is taken the last pick repeats."""
    nl, bs = mask.shape
    rows = np.arange(nl)

    def d2_to(i):
        c = pts[rows, i][:, None, :]
        sq = (pts - c) ** 2
        return (sq[..., 0] + sq[..., 1]) + sq[..., 2]

    start = np.argmax(mask, axis=1)
    out = np.empty((nl, k), np.int32)
    out[:, 0] = start
    m = np.where(mask, d2_to(start), NEG)
    m[rows, start] = NEG
    prev = start
    for t in range(1, k):
        has = m.max(axis=1) > NEG
        nxt = np.where(has, np.argmax(m, axis=1), prev)
        m = np.minimum(m, np.where(mask, d2_to(nxt), NEG))
        m[rows, nxt] = NEG
        out[:, t] = nxt
        prev = nxt
    return out


def sample(part, rate, k_out, bs):
    """Fixed-rate FPS per leaf, samples compacted leaf-major."""
    kbm = min(max(1, int(round(rate * bs)) + 1), bs)
    n = part["n"]
    rows, mask = _leaf_rows(part["start"], part["vsize"], part["is_leaf"],
                            n, bs)
    local = fps_leaves(part["coords"][rows], mask, kbm)
    quota = np.round(np.float32(rate) * part["vsize"].astype(np.float32))
    quota = np.where(part["is_leaf"], np.minimum(quota.astype(np.int64), kbm),
                     0)
    gidx = np.clip(part["start"][:, None] + local, 0, n - 1)
    cum = np.concatenate([[0], np.cumsum(quota)])
    bmask = np.arange(kbm)[None, :] < quota[:, None]
    pos = np.where(bmask, cum[:-1, None] + np.arange(kbm)[None, :], k_out)
    keep = pos < k_out
    idx_c = np.zeros((k_out,), np.int64)
    idx_c[pos[keep]] = gidx[keep]
    valid_c = np.arange(k_out) < min(int(cum[-1]), k_out)
    centers = part["coords"][idx_c] * valid_c[:, None].astype(np.float32)
    return {"kbm": kbm, "quota": quota, "cum": cum, "bmask": bmask,
            "gidx": gidx, "pos": pos, "idx": idx_c, "valid": valid_c,
            "centers": centers.astype(np.float32), "k_out": k_out}


def ball_query(part, samp, radius, num, w):
    """Up to ``num`` nearest in-radius neighbours of every sample inside
    its leaf's window (empty slots repeat the nearest)."""
    widx, wmask = _window_rows(part, w)
    win = part["coords"][widx]
    cen = part["coords"][samp["gidx"]]
    bmask = samp["bmask"]
    r2 = np.float32(radius) ** 2
    d = _sqdist(cen, win)                                  # (L, kbm, w)
    d = np.where(wmask[:, None, :], d, INF)
    nl, kbm, _ = d.shape
    lidx, nd2 = _topk_min(d.reshape(nl * kbm, w), num)
    lidx, nd2 = lidx.reshape(nl, kbm, num), nd2.reshape(nl, kbm, num)
    nd2 = np.maximum(nd2, 0)
    in_r = (nd2 <= r2) & bmask[..., None]
    lidx = np.where(in_r, lidx, lidx[..., :1])
    g = np.take_along_axis(widx[:, None, :].repeat(kbm, 1), lidx, axis=-1)
    k_out = samp["k_out"]
    keep = samp["pos"] < k_out
    out_i = np.zeros((k_out, num), np.int64)
    out_m = np.zeros((k_out, num), bool)
    out_i[samp["pos"][keep]] = g[keep]
    out_m[samp["pos"][keep]] = in_r[keep]
    out_m[:, 0] = samp["valid"]
    return out_i, out_m


def interpolation(part, samp, wc, bs):
    """For every point of the partitioned cloud (original order): the three
    nearest samples of its leaf's parent subtree and their normalised
    inverse-squared-distance weights (zero weights where unwritten)."""
    n, depth = part["n"], part["depth"]
    quota, cum = samp["quota"], samp["cum"]
    is_leaf = part["is_leaf"]
    lvl = part["level"]
    shift = np.maximum(depth - np.maximum(lvl - 1, 0), 0)
    slot = part["slot"]
    pslot = (slot >> shift) << shift
    total = 1 << depth
    slo = np.clip(pslot, 0, total)
    shi = np.clip(pslot + (1 << shift), 0, total)
    occupied = part["leaf_slots_sorted"]
    la = np.searchsorted(occupied, slo, side="left")
    lb = np.searchsorted(occupied, shi, side="left")
    ca, cb = cum[la], cum[lb]
    own = cum[:-1]
    lo = np.minimum(np.maximum(own - np.maximum(0, (wc - quota) // 2), ca),
                    np.maximum(ca, cb - wc))
    k_out = samp["k_out"]
    cidx = lo[:, None] + np.arange(wc)[None, :]
    cmask = (cidx < cb[:, None]) & is_leaf[:, None]
    cidx = np.clip(cidx, 0, k_out - 1)
    cmask &= samp["valid"][cidx]
    cpts = samp["centers"][cidx]
    rows, fmask = _leaf_rows(part["start"], part["vsize"], is_leaf, n, bs)
    fine = part["coords"][rows]
    d = _sqdist(fine, cpts)                                # (L, bs, wc)
    d = np.where(cmask[:, None, :], d, INF)
    nl = d.shape[0]
    nidx, nd2 = _topk_min(d.reshape(nl * bs, wc), 3)
    nidx, nd2 = nidx.reshape(nl, bs, 3), nd2.reshape(nl, bs, 3)
    nd2 = np.maximum(nd2, 0)
    wgt = np.where(nd2 < INF, np.float32(1.0) / (nd2 + EPS_IDW),
                   np.float32(0))
    wsum = (wgt[..., 0] + wgt[..., 1]) + wgt[..., 2]
    wgt = np.where(wsum[..., None] > 0,
                   wgt / np.maximum(wsum, EPS_IDW)[..., None], 0)
    sidx = np.take_along_axis(cidx[:, None, :].repeat(bs, 1), nidx, axis=-1)
    idx3 = np.zeros((n, 3), np.int64)
    w3 = np.zeros((n, 3), np.float32)
    dest = part["perm"][rows[fmask]]
    idx3[dest] = sidx[fmask]
    w3[dest] = wgt[fmask]
    return idx3, w3.astype(np.float32)


def plan_cloud(coords, valid, cfg: dict, dim0: int = 0) -> list:
    """Index plan of one cloud through every SA stage: per stage the
    partition order, the grouped neighbour indices and masks, the sampled
    centres and their validity, and the propagation indices and weights."""
    th = cfg["th"]
    stages = cfg["sa_stages"]
    sizes = stage_sizes(coords.shape[0], stages)
    wc = max(16, int(2 * th * stages[0]["rate"]))
    plan = []
    c, v = np.asarray(coords, np.float32), np.asarray(valid, bool)
    for i, s in enumerate(stages):
        part = partition(c, v, th, dim0 if i == 0 else 0)
        samp = sample(part, s["rate"], sizes[i + 1], th)
        nb_idx, nb_mask = ball_query(part, samp, s["radius"], s["nsample"],
                                     2 * th)
        idx3, w3 = interpolation(part, samp, wc, th)
        plan.append({"perm": part["perm"], "nb_idx": nb_idx.astype(np.int32),
                     "nb_mask": nb_mask, "centers": samp["centers"],
                     "valid": samp["valid"], "idx3": idx3.astype(np.int32),
                     "w3": w3, "overflow": part["overflow"]})
        c, v = samp["centers"], samp["valid"]
    return plan


def stack_plans(plans: list) -> list:
    """Stack the plans of clouds of one size along a leading batch axis."""
    keys = ("perm", "nb_idx", "nb_mask", "centers", "valid", "idx3", "w3")
    return [{k: np.stack([p[i][k] for p in plans]) for k in keys}
            for i in range(len(plans[0]))]


# ---------------------------------------------------------------------------
# Dense part.
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _fp8(x):
    """Per-tensor scaled float8 (e4m3) rounding of a float32 operand; its
    gradient passes straight through, as in float8 training."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = amax / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


_fp8.defvjp(lambda x: (_fp8(x), None), lambda _, g: (g,))


def _dense(p, x, quant):
    w = p["w"]
    if quant == "fp8":
        x, w = _fp8(x), _fp8(w)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST) + p["b"]


def _ln(p, x, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.var(x, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["g"] + p["b"]


def _mlp(layers, x, quant):
    for p in layers:
        x = jax.nn.relu(_ln(p["ln"], _dense(p["dense"], x, quant)))
    return x


def forward_one(params, coords, plan, quant=None):
    """Per-point logits of one cloud (n, num_classes) from its plan."""
    skips = [(coords, coords)]
    for i, st in enumerate(plan):
        c, f = skips[-1]
        sc, sf = c[st["perm"]], f[st["perm"]]
        rel = sc[st["nb_idx"]] - st["centers"][:, None, :]
        g = jnp.concatenate([rel, sf[st["nb_idx"]]], axis=-1)
        h = _mlp(params["stages"][i]["mlp"], g, quant)
        h = jnp.where(st["nb_mask"][..., None], h, -3.0e38)
        pooled = jnp.max(h, axis=-2)
        pooled = jnp.where(st["nb_mask"].any(-1, keepdims=True), pooled, 0.0)
        skips.append((st["centers"], pooled))
    up = skips[-1][1]
    for i, layers in enumerate(params["fp"]):
        lvl = len(plan) - 1 - i
        st = plan[lvl]
        vals = up[st["idx3"]]                               # (n, 3, C)
        w = st["w3"]
        interp = (vals[:, 0] * w[:, 0:1] + vals[:, 1] * w[:, 1:2]) \
            + vals[:, 2] * w[:, 2:3]
        up = _mlp(layers, jnp.concatenate([interp, skips[lvl][1]], -1),
                  quant)
    h = _mlp(params["head"], up, quant)
    return _dense(params["out"], h, quant)


def forward(params, coords, plans, quant=None):
    """Batched ``forward_one``: coords (B, n, 3), stacked plans."""
    return jax.vmap(lambda c, p: forward_one(params, c, p, quant))(
        coords, plans)


def seg_loss(params, coords, labels, plans, quant=None):
    """Mean cross-entropy over every point of the batch."""
    logits = forward(params, coords, plans, quant)
    ll = jax.nn.log_softmax(logits)
    picked = jnp.take_along_axis(ll, labels[..., None], axis=-1)[..., 0]
    return -jnp.mean(picked)


# ---------------------------------------------------------------------------
# Parameters, made from the seed by the benchmark (never by the program).
# ---------------------------------------------------------------------------

def param_shapes(cfg: dict) -> dict:
    """Layer widths of the network, as (din, dout) per dense layer."""
    stages = cfg["sa_stages"]
    c_in = cfg["in_channels"]
    sa = []
    for s in stages:
        dims, d = [], c_in + 3
        for w in s["mlp"]:
            dims.append((d, w))
            d = w
        sa.append(dims)
        c_in = s["mlp"][-1]
    skip = [cfg["in_channels"]] + [s["mlp"][-1] for s in stages[:-1]]
    up = stages[-1]["mlp"][-1]
    fp = []
    for i, widths in enumerate(cfg["fp_mlp"]):
        dims, d = [], up + skip[-(i + 1)]
        for w in widths:
            dims.append((d, w))
            d = w
        fp.append(dims)
        up = widths[-1]
    head, d = [], up
    for w in cfg["head_mlp"]:
        head.append((d, w))
        d = w
    return {"sa": sa, "fp": fp, "head": head, "out": (d, cfg["num_classes"])}


def init_params(key, cfg: dict):
    """Random float32 weights in the program's parameter layout: dense
    weights scaled by sqrt(2 / (din + dout)), biases and LayerNorm offsets
    drawn around 0, LayerNorm gains around 1 (so that every parameter
    carries signal)."""
    shapes = param_shapes(cfg)
    keys = iter(jax.random.split(key, 4096))

    def dense(din, dout):
        w = jax.random.normal(next(keys), (din, dout), jnp.float32)
        b = jax.random.normal(next(keys), (dout,), jnp.float32)
        return {"w": w * (2.0 / (din + dout)) ** 0.5, "b": 0.1 * b}

    def layer(din, dout):
        g = jax.random.normal(next(keys), (dout,), jnp.float32)
        b = jax.random.normal(next(keys), (dout,), jnp.float32)
        return {"dense": dense(din, dout),
                "ln": {"g": 1.0 + 0.1 * g, "b": 0.1 * b}}

    return {
        "stages": [{"mlp": [layer(*d) for d in dims]}
                   for dims in shapes["sa"]],
        "fp": [[layer(*d) for d in dims] for dims in shapes["fp"]],
        "head": [layer(*d) for d in shapes["head"]],
        "out": dense(*shapes["out"]),
    }
