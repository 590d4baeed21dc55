"""Serving driver: an open-loop stream of clouds through ``ServeEngine``.

Set-up makes the weights and the cloud pool from the seed, builds the
engine with the one bucket the traffic uses, runs ``warm()`` and then a
full and a partial microbatch through ``submit``/``step``, so that nothing
the window drives compiles inside it.

The window offers the traffic file's Poisson stream: each request is
submitted once it is due, with its due time as its submission time, and
timed from that due time to the return of the ``step()`` call that hands
its logits back.  After the last arrival the loop goes on until every
request due in the window has been answered.

The check compares a seeded sample of the answered requests with the
reference, once the window has closed and the engine is gone.
"""
from __future__ import annotations

import collections
import time

import numpy as np

from bench import arrivals, clouds, compare, model, reference, trace


def _sleep_until(t_wake):
    while True:
        rem = t_wake - time.monotonic()
        if rem <= 0:
            return
        if rem > 0.0015:
            time.sleep(rem - 0.001)


def build(r):
    """Weights, cloud pool and a warmed engine for the cell."""
    import jax
    from repro import serve

    cfg, tr = r.cfg, r.traffic
    sv = tr["serve"]
    params = model.weights(cfg, r.key)
    pts, _ = clouds.pool(r.seed, tr["clouds"])
    eng = serve.ServeEngine(serve.ServeConfig(
        buckets=(sv["bucket"],), microbatch=sv["microbatch"],
        max_wait_s=sv["max_wait_s"], task=cfg["task"],
        on_overflow=sv["on_overflow"], **model.model_overrides(cfg)),
        params=params)
    eng.warm()
    # The eager ops of a full and of a partial microbatch compile here.
    for k in (sv["microbatch"], 1):
        for i in range(k):
            eng.submit(pts[i])
        for rid in eng.step() + eng.flush():
            eng.take(rid)
    jax.block_until_ready(params)
    return params, pts, eng


def window(eng, pts, due, pick, keep, max_wait_s, seconds, tracing=None,
           trace_seconds=0.0):
    """Offer the stream: request j (cloud ``pts[pick[j]]``) is submitted
    once ``due[j]`` seconds of the window have passed.  Returns the window
    start, each request's completion time (NaN if never answered), how
    late each was submitted, the kept results and the requests answered
    while the profiler ran."""
    n_req = len(due)
    done_at = np.full(n_req, np.nan)
    late = np.zeros(n_req)
    results = {}
    rid_req = {}
    pending = collections.deque()
    served_in_trace = 0
    t0 = time.monotonic()
    if tracing:
        tracing.start()
    i = 0
    give_up = t0 + seconds + 60.0
    while True:
        now = time.monotonic()
        if tracing and tracing.active and now - t0 >= trace_seconds:
            tracing.stop()
        if i < n_req and t0 + due[i] <= now:
            with trace.span("bench.admit", tracing):
                while i < n_req and t0 + due[i] <= now:
                    rid = eng.submit(pts[pick[i]], now=t0 + due[i])
                    late[i] = time.monotonic() - (t0 + due[i])
                    rid_req[rid] = i
                    pending.append(i)
                    i += 1
        with trace.span("serve.step", tracing):
            done = eng.step()
        if done:
            t = time.monotonic()
            for rid in done:
                j = rid_req.pop(rid)
                done_at[j] = t
                res = eng.take(rid)
                if j in keep:
                    results[j] = res
            if tracing and tracing.active:
                served_in_trace += len(done)
            while pending and not np.isnan(done_at[pending[0]]):
                pending.popleft()
            continue
        if i >= n_req and not pending:
            break
        if time.monotonic() > give_up:
            break
        wake = t0 + due[i] if i < n_req else np.inf
        if pending:
            wake = min(wake, t0 + due[pending[0]] + max_wait_s)
        if tracing and tracing.active:
            wake = min(wake, t0 + trace_seconds)
        with trace.span("bench.wait", tracing):
            _sleep_until(min(wake, give_up))
    if tracing and tracing.active:
        tracing.stop()
    return t0, done_at, late, results, served_in_trace


def run(r):
    cfg, tr = r.cfg, r.traffic
    params, pts, eng = build(r)
    arr = tr["arrivals"]
    due = arrivals.poisson_due(arr["rate_per_s"], r.seconds, r.seed,
                               arr["order_seed"])
    n_req = len(due)
    pick = clouds.rng_for(r.seed, 102).integers(0, len(pts), n_req)
    n_check = min(tr["check"]["sample"], n_req)
    keep = set(int(j) for j in clouds.rng_for(r.seed, 103).choice(
        n_req, n_check, replace=False))
    r.setup_done()

    tracing = trace.Window(r.trace_dir) if r.trace else None
    t0, done_at, late, results, served_in_trace = window(
        eng, pts, due, pick, keep, tr["serve"]["max_wait_s"], r.seconds,
        tracing, min(r.trace_seconds, r.seconds))
    r.window_done()
    answered = np.isfinite(done_at)
    t_last = np.max(done_at[answered])
    lat = arrivals.latencies(due, t0, done_at)[answered]
    r.memory_peak()
    del eng
    r.e2e["serve_clouds_per_s"] = float(answered.sum() / (t_last - t0))
    r.attempted, r.failed = n_req, int(n_req - answered.sum())
    r.info(f"requests={n_req} answered={int(answered.sum())} "
           f"rate_per_s={tr['arrivals']['rate_per_s']} "
           f"latency_p50_ms={np.percentile(lat, 50) * 1e3} "
           f"latency_p95_ms={np.percentile(lat, 95) * 1e3} "
           f"latency_p99_ms={np.percentile(lat, 99) * 1e3} "
           f"latency_max_ms={lat.max() * 1e3} "
           f"drain_s={t_last - t0 - r.seconds} "
           f"generator_late_p50_ms={np.median(late) * 1e3} "
           f"generator_late_max_ms={late.max() * 1e3}")
    if tracing:
        flops = model.dense_flops(cfg, tr["clouds"]["points"]).total
        r.traced(tracing, units=None, work_flops=served_in_trace * flops)

    # The check: a seeded sample of the answered requests.
    lost = sum(1 for j in keep if j not in results)
    js = sorted(results)
    bucket = tr["serve"]["bucket"]
    clouds_ = [pad(pts[pick[j]], bucket) for j in js]
    plans = r.map(lambda cv: reference.plan_cloud(cv[0], cv[1], cfg),
                  clouds_)
    want = r.reference_logits(params, np.stack([c for c, _ in clouds_]),
                              plans)
    gaps = [compare.logit_gap(results[j], w[:len(results[j])])
            for j, w in zip(js, want)]
    r.check({"logit_gap": max(gaps) if gaps else float("inf"),
             "unanswered": n_req - int(answered.sum()) + lost})


def pad(cloud, bucket):
    """A cloud as admission pads it: zeros past its points, masked."""
    c = np.zeros((bucket, 3), np.float32)
    c[:len(cloud)] = cloud
    return c, np.arange(bucket) < len(cloud)
