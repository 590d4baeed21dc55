"""Fine-tuning driver: AdamW steps through ``train.pnn.make_train_step``.

Set-up makes the weights and a pool of batches from the seed, builds the
one jitted step, and drives it through its first three steps on the first
three batches (the first compiles).  The window goes on with the same
step, parameters and optimizer state over the rest of the pool, keeping
at most two steps in flight, until ``--seconds`` have passed, and ends
when its last step has finished.

The check follows the first three steps with the reference: the first
gradient as the optimizer received it (read back from its first moment
after one step) and the change of every parameter over the three steps,
each as the gap of the program's and the reference's norms, leaf by leaf.
The median leaf is compared (``bench/limits/``); the worst leaf, the
losses and the worst leaves' names are printed.  The worst leaf is a
different small leaf on every seed (a first-layer weight or bias, a
LayerNorm gain, a bias's change), moved by rounding alone, and the
losses part by rounding too: Adam's first steps move every weight by about
the learning rate whatever the size of its gradient.
"""
from __future__ import annotations

import collections
import time

import numpy as np

from bench import clouds, compare, model, reference, trace


def opt_config(cfg: dict):
    from repro.train import optimizer as opt_lib
    o = cfg["optimizer"]
    return opt_lib.OptConfig(lr=o["lr"], min_lr_frac=o["min_lr_frac"],
                             warmup=o["warmup"], total_steps=o["total_steps"],
                             b1=o["b1"], b2=o["b2"], eps=o["eps"],
                             weight_decay=o["weight_decay"],
                             clip_norm=o["clip_norm"])


def make_step(cfg: dict):
    """The system under test: one jitted step and a fresh optimizer
    state."""
    from repro.train import optimizer as opt_lib
    from repro.train import pnn as tpnn
    step = tpnn.make_train_step(model.pnn_config(cfg), opt_config(cfg))
    return step, opt_lib.init


def _leaves(tree) -> dict:
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): np.asarray(v) for k, v in flat}


def reference_steps(cfg, params, batches, plans, steps, quant=None):
    """The reference's losses, its first clipped gradient and its
    parameters after ``steps`` AdamW steps (plain float32 jax.numpy)."""
    import jax
    import jax.numpy as jnp
    o = cfg["optimizer"]
    grad = jax.jit(jax.value_and_grad(
        lambda p, c, l, pl: reference.seg_loss(p, c, l, pl, quant)))
    p = params
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, first = [], None
    for t in range(1, steps + 1):
        c, lab = batches[t - 1]
        loss, g = grad(p, jnp.asarray(c), jnp.asarray(lab), plans[t - 1])
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        g = jax.tree.map(
            lambda x: x * jnp.minimum(1.0, o["clip_norm"] / (gn + 1e-9)), g)
        if first is None:
            first = g
        frac = min(t / max(o["warmup"], 1), 1.0)
        prog = min(max((t - o["warmup"]) /
                       max(o["total_steps"] - o["warmup"], 1), 0.0), 1.0)
        lr = o["lr"] * frac * (o["min_lr_frac"] + (1 - o["min_lr_frac"]) *
                               0.5 * (1 + np.cos(np.pi * prog)))
        m = jax.tree.map(lambda a, b: o["b1"] * a + (1 - o["b1"]) * b, m, g)
        v = jax.tree.map(lambda a, b: o["b2"] * a + (1 - o["b2"]) * b * b,
                         v, g)

        def upd(pp, mm, vv):
            d = (mm / (1 - o["b1"] ** t)) / (
                jnp.sqrt(vv / (1 - o["b2"] ** t)) + o["eps"])
            if pp.ndim >= 2:
                d = d + o["weight_decay"] * pp
            return pp - lr * d
        p = jax.tree.map(upd, p, m, v)
        losses.append(float(loss))
    return losses, first, p


def pool(r):
    """The seed's pool of batches, as numpy points (B, b, n, 3) and labels
    (B, b, n)."""
    cfg, tr = r.cfg, r.traffic
    b = cfg["batch"]
    pts, lab = clouds.pool(r.seed, dict(tr["clouds"],
                                        pool=tr["pool_batches"] * b))
    n = pts.shape[1]
    return (pts.reshape(tr["pool_batches"], b, n, 3),
            lab.reshape(tr["pool_batches"], b, n))


def first_steps(cfg, step, opt_init, params, batches, k):
    """Drive ``step`` from fresh state through its first ``k`` steps.
    Returns the parameters and state after them, and what the check
    compares: the losses, the first gradient as the optimizer received it
    (its first moment after one step, unbiased) and each leaf's change."""
    import jax
    b1 = cfg["optimizer"]["b1"]
    p, st = params, opt_init(params)
    losses = []
    for i in range(k):
        p, st, met = step(p, st, batches[i])
        losses.append(met["loss"])
        if i == 0:
            m1 = st["m"]
    jax.block_until_ready((p, st))
    got = ([float(x) for x in losses],
           {q: v / (1 - b1) for q, v in _leaves(m1).items()},
           {q: a - c for (q, a), c in zip(_leaves(p).items(),
                                          _leaves(params).values())})
    return p, st, got


def reference_first(r, params, pts, lab, quant=None, rows=None):
    """The reference's first steps on the seed's first batches, in the
    form ``first_steps`` returns: with ``quant`` its operands rounded to
    that format, with ``rows`` only the first rows of each batch."""
    cfg, k = r.cfg, r.traffic["checked_steps"]
    pts, lab = pts[:k, :rows], lab[:k, :rows]
    n = pts.shape[2]
    plans = [reference.stack_plans(r.map(
        lambda c: reference.plan_cloud(c, np.ones(n, bool), cfg), pts[i]))
        for i in range(k)]
    losses, first, p = reference_steps(
        cfg, params, list(zip(pts, lab)), plans, k, quant)
    return losses, _leaves(first), {q: a - c for (q, a), c in zip(
        _leaves(p).items(), _leaves(params).values())}


def numbers(r, got, want):
    """The numbers the check compares, between first steps ``got`` and the
    reference's ``want``, with the look at the worst leaves as info lines.
    Leaves whose reference gradient is under a thousandth of the median
    leaf's are left out of the change."""
    got_loss, got_first, got_change = got
    ref_loss, ref_first, ref_change = want
    norms = {q: float(np.linalg.norm(v)) for q, v in ref_first.items()}
    med = float(np.median(list(norms.values())))
    still = {q for q, v in norms.items() if v < 1e-3 * med}
    loss_gaps = [abs(a - c) / abs(c) for a, c in zip(got_loss, ref_loss)]
    r.info(f"reference_losses={ref_loss} loss_gaps={loss_gaps} "
           f"leaves={len(norms)} leaves_left_out_of_change={sorted(still)}")
    grad = compare.leaf_gaps(got_first, ref_first)
    change = compare.leaf_gaps(got_change, ref_change, exclude=still)
    for name, gaps, ref in (("first_grad", grad, ref_first),
                            ("change", change, ref_change)):
        worst = sorted(gaps, key=gaps.get, reverse=True)[:3]
        ref_med = np.median([np.linalg.norm(v) for v in ref.values()])
        r.info(f"worst_leaves.{name}=" + "; ".join(
            f"{q} gap={gaps[q]} norm={np.linalg.norm(ref[q])} "
            f"size={ref[q].size}" for q in worst) +
            f" median_norm={ref_med}")
    return {
        "first_loss_gap": loss_gaps[0],
        "first_grad_gap": max(grad.values()),
        "first_grad_median": float(np.median(list(grad.values()))),
        "change_gap": max(change.values()),
        "change_median": float(np.median(list(change.values()))),
    }


def run(r):
    import jax

    cfg, tr = r.cfg, r.traffic
    pts, lab = pool(r)
    b, n = pts.shape[1], pts.shape[2]
    batches = [{"points": jax.device_put(pts[i]),
                "labels": jax.device_put(lab[i])} for i in range(len(pts))]
    params = model.weights(cfg, r.key)
    step, opt_init = make_step(cfg)
    first = tr["checked_steps"]
    p, st, got = first_steps(cfg, step, opt_init, params, batches, first)
    r.setup_done()

    tracing = trace.Window(r.trace_dir) if r.trace else None
    trace_steps = tr["trace_steps"]
    inflight = collections.deque()
    steps = 0
    t0 = time.monotonic()
    if tracing:
        tracing.start()
    while True:
        with trace.span("train.step", tracing):
            p, st, met = step(p, st, batches[(first + steps) %
                                             len(batches)])
        steps += 1
        inflight.append(met["loss"])
        if len(inflight) > 2:
            with trace.span("train.wait", tracing):
                inflight.popleft().block_until_ready()
        if tracing and tracing.active and steps == trace_steps:
            jax.block_until_ready((p, st))
            tracing.stop()
        if time.monotonic() - t0 >= r.seconds and not (
                tracing and tracing.active):
            break
    jax.block_until_ready((p, st))
    t1 = time.monotonic()
    r.window_done()
    r.memory_peak()
    del p, st, met, inflight, step, batches
    r.e2e["train_step_ms"] = (t1 - t0) / steps * 1e3
    r.attempted, r.failed = steps, 0
    r.info(f"steps={steps} window_s={t1 - t0} batch={b} points={n} "
           f"losses={got[0]}")
    if tracing:
        flops = 3 * b * model.dense_flops(cfg, n).total
        r.traced(tracing, units=trace_steps,
                 work_flops=trace_steps * flops)
    r.check(numbers(r, got, reference_first(r, params, pts, lab)))
