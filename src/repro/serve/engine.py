"""The PNN serving engine: admission -> queue -> plan cache -> dispatch.

One engine owns the whole deployment path of docs/DESIGN.md §9:

* admission pads each cloud to its minimal shape bucket (``bucketing``);
* a per-bucket microbatch queue packs requests under a max-wait deadline
  (``batching``); partial batches are padded with all-invalid clouds so
  executable shapes never vary;
* a plan cache holds one jitted fractal-partition plan per
  (bucket, th, strategy) and one jitted forward per (bucket, impl)
  (``plan_cache``) — the plan phase is traced once per bucket, not once
  per request batch, mirroring the bppo plan/execute split (§4);
* microbatches optionally shard over an elastic mesh via ``repro.dist``
  (``elastic.make_mesh`` + ``logical.fit_specs``): clouds -> ``data``,
  fractal leaves -> ``model`` (§6);
* each boundary is a host span (``spans``), recorded only while the
  profiler runs: ``serve.admit`` per request, ``serve.execute`` per
  microbatch with its children ``serve.assemble``, ``serve.plan``,
  ``serve.forward``, ``serve.sync`` and ``serve.fetch``.

The engine is synchronous and deterministic: time enters only through its
clock (injectable for tests), and ``warm()`` compiles every executable
up front so reported latencies never include compile time.
"""
from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro import core
from repro.dist import elastic, logical
from repro.kernels import ops as kops
from repro.models import pnn
from repro.serve.batching import MicroBatch, MicroBatchQueue
from repro.serve.bucketing import DEFAULT_BUCKETS, BucketPolicy
from repro.serve.plan_cache import PlanCache
from repro.serve.spans import recording, span

# Requests per bucket whose latencies stats() keeps for its percentiles.
LATENCY_WINDOW = 65_536


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Serving-time knobs (model structure + admission + dispatch)."""

    buckets: tuple = DEFAULT_BUCKETS
    microbatch: int = 4
    max_wait_s: float = 0.02       # deadline for partial microbatches
    variant: str = "pointnet2"     # pointnet2 | pointnext | pointvector
    task: str = "seg"              # cls | seg
    num_classes: int = 6
    th: int = 256                  # fractal threshold (plan-cache key part)
    strategy: str = "fractal"      # partition strategy (plan-cache key part)
    point_ops: str = "bppo"        # bppo | global
    impl: str | None = None        # xla | pallas | None ($REPRO_POINT_IMPL)
    leaf_chunk: int | None = None
    mesh: str = "none"             # none | auto (elastic host mesh)
    model_axis: int = 2            # elastic mesh model-axis request
    stages: tuple | None = None    # override PNNConfig.stages (scene uses
    fp_widths: tuple | None = None  # a single-SA-stage model, §10)
    on_overflow: str = "warn"      # partition-plan depth-cap overflow:
                                   # warn (async callback, ~free next to a
                                   # forward) | silent


class ServeEngine:
    """Shape-bucketed, plan-cached PNN serving (DESIGN.md §9)."""

    def __init__(self, cfg: ServeConfig, params=None, mesh=None, seed=0,
                 clock=time.monotonic):
        self.cfg = cfg
        # Pinned once: flipping $REPRO_POINT_IMPL mid-serve must not
        # bifurcate the executable cache.
        self.impl = kops.resolve_impl(cfg.impl, default="xla")
        self.policy = BucketPolicy(cfg.buckets)
        self.queue = MicroBatchQueue(self.policy, cfg.microbatch,
                                     cfg.max_wait_s)
        self.plans = PlanCache()
        self._clock = clock
        if mesh is not None:
            self.mesh = mesh
        elif cfg.mesh == "auto":
            self.mesh = elastic.make_mesh(model_axis=cfg.model_axis)
        else:
            self.mesh = None
        overrides = {k: getattr(cfg, k) for k in ("stages", "fp_widths")
                     if getattr(cfg, k) is not None}
        self._base = pnn.PNNConfig(
            name=f"serve_{cfg.variant}_{cfg.task}", variant=cfg.variant,
            task=cfg.task, num_classes=cfg.num_classes,
            n_points=self.policy.buckets[0], point_ops=cfg.point_ops,
            th=cfg.th, strategy=cfg.strategy, impl=self.impl,
            leaf_chunk=cfg.leaf_chunk, **overrides)
        self.params = (params if params is not None
                       else pnn.init(jax.random.PRNGKey(seed), self._base))
        self.results: dict[int, np.ndarray] = {}
        self._lat = {b: collections.deque(maxlen=LATENCY_WINDOW)
                     for b in self.policy.buckets}
        self._served = collections.Counter()   # bucket -> clouds answered
        self._points = collections.Counter()   # bucket -> their points
        # The plan of the last forward(), kept only while the profiler runs,
        # for _execute's leaf counters.
        self._traced_part = None
        self.compile_s: dict[int, float] = {}
        self._t_first: float | None = None
        self._t_last: float | None = None

    # -- executables ------------------------------------------------------

    def _model_cfg(self, bucket: int) -> pnn.PNNConfig:
        return dataclasses.replace(self._base, n_points=bucket)

    def _plan_fn(self, bucket: int):
        key = ("plan", bucket, self.cfg.th, self.cfg.strategy)
        th, strategy = self.cfg.th, self.cfg.strategy
        on_overflow = self.cfg.on_overflow

        def build():
            # dim0 is a traced (B,) input, not part of the key: phasing
            # the split-dimension cycle per cloud (scene tiles) reuses the
            # one cached plan executable.  on_overflow="warn" (default)
            # surfaces depth-cap overflow in admitted clouds — e.g. an
            # unsplittable duplicate cluster bigger than th inside a
            # scene tile — via an async callback whose cost is noise next
            # to the forward it gates.
            def plan(clouds, valid, dim0):
                return jax.vmap(lambda c, v, d: core.partition(
                    c, v, th=th, strategy=strategy, dim0=d,
                    on_overflow=on_overflow))(clouds, valid, dim0)
            return plan

        return self.plans.get(key, build)

    def _serve_fn(self, bucket: int):
        key = ("serve", bucket, self.impl)
        mcfg = self._model_cfg(bucket)
        mesh = self.mesh

        if self.cfg.point_ops == "bppo":
            def build():
                def step(params, clouds, valid, part):
                    return pnn.apply_batch(params, mcfg, clouds, valid,
                                           part0=part, mesh=mesh)
                return step
        else:
            def build():
                def step(params, clouds, valid):
                    return pnn.apply_batch(params, mcfg, clouds, valid,
                                           mesh=mesh)
                return step

        return self.plans.get(key, build)

    def _run(self, fn, *args):
        """Call (and on first use, trace) ``fn`` under the mesh's logical
        rules so ``lc`` constraints bake into the executable."""
        if self.mesh is None:
            return fn(*args)
        with logical.logical_rules(self.mesh, logical.RULES_V0):
            return fn(*args)

    def _device_put_batch(self, clouds, valid):
        """Shard one microbatch over the mesh: clouds -> the data axes,
        specs fitted against actual shapes (non-dividing axes drop) — or,
        for the Pallas point ops, over the axes ``pnn.apply_batch`` splits
        the clouds over."""
        if self.mesh is None:
            return clouds, valid
        split = pnn.batch_spec(self._base, self.mesh, clouds.shape[0])
        if split is not None:
            sh = NamedSharding(self.mesh, split)
            return jax.device_put((clouds, valid), (sh, sh))
        with logical.logical_rules(self.mesh, logical.RULES_V0):
            sh = (NamedSharding(self.mesh,
                                logical.spec(("batch", "points", None))),
                  NamedSharding(self.mesh, logical.spec(("batch",
                                                         "points"))))
        sh = logical.fit_specs(sh, (clouds, valid), self.mesh)
        return jax.device_put((clouds, valid), sh)

    def _filler(self, bucket: int):
        """One microbatch of all-invalid clouds — the filler ``_execute``
        pads partial batches with.  (All-*valid* zeros would be ``bucket``
        duplicate points: unsplittable, so warming on them would emit a
        spurious partition-overflow warning.)"""
        mb = self.queue.microbatch
        return (jnp.zeros((mb, bucket, 3), jnp.float32),
                jnp.zeros((mb, bucket), bool), jnp.zeros((mb,), jnp.int32))

    def executable(self, bucket: int):
        """The compiled serve executable of ``bucket`` (its HLO, cost and
        memory analyses), lowered with the arguments placed as a
        microbatch is.  After ``warm()`` this compiles nothing new."""
        clouds, valid, dim0 = self._filler(bucket)
        clouds, valid = self._device_put_batch(clouds, valid)
        args = (self.params, clouds, valid)
        if self.cfg.point_ops == "bppo":
            plan = self._run(lambda *a: self._plan_fn(bucket).lower(*a),
                             clouds, valid, dim0)
            # The partition as the plan executable returns it, placement
            # included, so the serve executable compiled here is the one
            # forward() calls.
            args += (jax.tree.map(
                lambda o, sh: jax.ShapeDtypeStruct(o.shape, o.dtype,
                                                   sharding=sh),
                plan.out_info, plan.compile().output_shardings),)
        return self._run(lambda *a: self._serve_fn(bucket).lower(*a)
                         .compile(), *args)

    # -- serving ----------------------------------------------------------

    def warm(self, buckets=None) -> dict[int, float]:
        """Compile the plan + serve executables per bucket and run each
        once on an all-invalid microbatch, so request latencies exclude
        compile.  Returns {bucket: compile_seconds}.

        The buckets compile on two threads, largest first (XLA compiles
        without holding the GIL, so the compiles overlap on the host's
        cores; the largest bucket's compile takes about as long as the
        rest together, so a third thread would save little time and add a
        third compile's host memory, ~5 GB for the default model); the
        runs then reuse those executables one bucket at a time, so the
        device never holds two buckets' temporaries."""
        # Compile timing is deliberately real wall time, not self._clock():
        # an injected logical clock cannot time actual XLA compile work,
        # and compile_s is reported separately from the request-latency
        # clock domain (stats() never mixes them).
        buckets = tuple(buckets if buckets is not None
                        else self.policy.buckets)
        # The matmul precision is thread-local: compile under the caller's.
        precision = jax.config.jax_default_matmul_precision

        def compile_one(b):
            t0 = time.monotonic()  # repolint: disable=CLK001
            with jax.default_matmul_precision(precision):
                self.executable(b)
            return time.monotonic() - t0  # repolint: disable=CLK001

        order = sorted(buckets, reverse=True)
        with concurrent.futures.ThreadPoolExecutor(2) as pool:
            spent = dict(zip(order, pool.map(compile_one, order)))
        for b in buckets:
            t0 = time.monotonic()  # repolint: disable=CLK001
            jax.block_until_ready(self.forward(b, *self._filler(b)))
            run_s = time.monotonic() - t0  # repolint: disable=CLK001
            self.compile_s[b] = spent[b] + run_s
        return dict(self.compile_s)

    def forward(self, bucket, clouds, valid, dim0):
        """Run one microbatch — clouds (microbatch, bucket, 3), valid
        (microbatch, bucket), dim0 (microbatch,) — through the bucket's
        plan and serve executables; returns the logits as placed on the
        device (sharded over the mesh, if any)."""
        clouds, valid = self._device_put_batch(clouds, valid)
        if self.cfg.point_ops != "bppo":
            with span("serve.forward"):
                return self._run(self._serve_fn(bucket), self.params,
                                 clouds, valid)
        with span("serve.plan"):
            part = self._run(self._plan_fn(bucket), clouds, valid, dim0)
        if recording():
            self._traced_part = part
        with span("serve.forward"):
            return self._run(self._serve_fn(bucket), self.params, clouds,
                             valid, part)

    def submit(self, coords, now: float | None = None, dim0: int = 0) -> int:
        """Admit one (n, 3) cloud; returns the request id.

        ``dim0`` phases the cloud's fractal-partition plan (split dimension
        of level l is (l + dim0) % 3) — the scene executor passes each
        tile's coarse-tree depth so the tile's local tree extends the
        global one (docs/DESIGN.md §10).  It is a traced plan input, so it
        never grows the executable cache."""
        now = self._clock() if now is None else now
        with span("serve.admit") as sp:
            coords = jnp.asarray(coords, jnp.float32)
            req = self.queue.submit(coords, now, dim0=dim0)
            sp.set(rid=req.rid, bucket=req.bucket)
        if self._t_first is None:
            self._t_first = now
        return req.rid

    def step(self, now: float | None = None) -> list[int]:
        """Dispatch every microbatch that is ready at ``now`` (full, or
        past its deadline).  Returns the completed request ids.

        An injected ``now`` is threaded through to completion stamping, so
        latencies stay in the caller's clock domain (see ``_execute``)."""
        return self._execute_all(
            self.queue.ready(self._clock() if now is None else now), now)

    def flush(self, now: float | None = None) -> list[int]:
        """Drain the queue (end of stream), deadline or not."""
        return self._execute_all(self.queue.drain(), now)

    def _execute_all(self, mbs: list, now: float | None) -> list[int]:
        """Run microbatches in order.  The queue hands over every ready
        microbatch at once, so the requests of a bucket still waiting for
        dispatch are those of its later microbatches plus its pending
        ones (``serve.execute``'s ``depth``)."""
        behind = collections.Counter()
        for mb in mbs:
            behind[mb.bucket] += len(mb.requests)
        done = []
        for mb in mbs:
            behind[mb.bucket] -= len(mb.requests)
            done.extend(self._execute(mb, now, behind[mb.bucket]))
        return done

    def take(self, rid: int, default=None):
        """Pop a completed result (clients should prefer this over reading
        ``results`` directly: a long-running engine must not accumulate
        one array per request forever)."""
        return self.results.pop(rid, default)

    def _execute(self, mb: MicroBatch, now: float | None = None,
                 behind: int = 0) -> list[int]:
        """Run one microbatch.  ``now`` is the caller-injected logical time
        (from ``step(now=)``/``flush(now=)``): when present, completions
        are stamped with it so latencies and ``wall_s`` never mix the
        injected clock domain with the engine's real clock; when absent,
        the engine clock is read *after* execution so real latencies
        include the forward.  ``behind`` counts the bucket's requests in
        microbatches already taken from the queue after this one."""
        bucket, reqs = mb.bucket, mb.requests
        npad = self.queue.microbatch - len(reqs)

        def waited_ms():
            t = self._clock() if now is None else now
            return 1e3 * sum(t - r.t_submit for r in reqs)

        with span("serve.execute",
                  rids=lambda: " ".join(str(r.rid) for r in reqs),
                  requests=len(reqs), slots=self.queue.microbatch,
                  wait_ms=waited_ms,
                  depth=lambda: behind + self.queue.pending(bucket)) as sp:
            with span("serve.assemble"):
                clouds = jnp.stack(
                    [r.coords for r in reqs]
                    + [jnp.zeros((bucket, 3), jnp.float32)] * npad)
                valid = jnp.stack([r.valid for r in reqs]
                                  + [jnp.zeros((bucket,), bool)] * npad)
                dim0 = jnp.asarray([r.dim0 for r in reqs] + [0] * npad,
                                   jnp.int32)
            out = self.forward(bucket, clouds, valid, dim0)
            with span("serve.sync"):
                jax.block_until_ready(out)
            t_done = self._clock() if now is None else now
            part, self._traced_part = self._traced_part, None
            if part is not None:
                # Stage-0 leaves of the real clouds, against their slots;
                # the plan has finished, so the fetch adds no sync.
                n_real = len(reqs)
                sp.set(leaves=lambda: int(
                    np.asarray(part.num_leaves)[:n_real].sum()),
                    leaf_slots=n_real * part.leaf_start.shape[-1])
            with span("serve.fetch"):
                out = np.asarray(out)
                rids = []
                for i, r in enumerate(reqs):
                    res = out[i][:r.n] if self.cfg.task == "seg" else out[i]
                    self.results[r.rid] = res
                    self._lat[bucket].append((t_done - r.t_submit, r.n))
                    self._served[bucket] += 1
                    self._points[bucket] += r.n
                    rids.append(r.rid)
        self._t_last = t_done
        return rids

    # -- reporting --------------------------------------------------------

    def stats(self) -> dict:
        """Per-bucket latency percentiles + sustained throughput + plan
        cache counters (the BENCH_serve.json payload).

        ``count`` and the throughput count every request answered; the
        percentiles and ``mean_ms`` cover each bucket's most recent
        ``LATENCY_WINDOW`` (65,536) requests.

        Throughput (``wall_s``, ``clouds_per_s``, ``mpts_per_s``) is
        ``None`` until at least one microbatch has completed *and* the
        first-submit -> last-completion window has positive width: a
        submit-only stream has no window at all, and an injected clock
        can complete a batch at the very instant of its submit — either
        way, dividing by an epsilon clamp would report absurd numbers
        instead of "unknown" (benchmarks/serve_bench.py skips the None
        rows)."""
        buckets = {}
        served = sum(self._served.values())
        points = sum(self._points.values())
        wall = None
        if (self._t_first is not None and self._t_last is not None
                and self._t_last > self._t_first):
            wall = self._t_last - self._t_first
        for b, lat in self._lat.items():
            if not lat:
                continue
            ls = np.asarray([l for l, _ in lat])
            count = self._served[b]
            buckets[b] = {
                "count": count,
                "p50_ms": float(np.percentile(ls, 50) * 1e3),
                "p95_ms": float(np.percentile(ls, 95) * 1e3),
                "p99_ms": float(np.percentile(ls, 99) * 1e3),
                "mean_ms": float(ls.mean() * 1e3),
                "clouds_per_s": count / wall if wall is not None else None,
                "compile_s": self.compile_s.get(b),
            }
        return {"impl": self.impl, "served": served, "wall_s": wall,
                "clouds_per_s": served / wall if wall is not None else None,
                "mpts_per_s": (points / wall / 1e6
                               if wall is not None else None),
                "buckets": buckets, "plan_cache": self.plans.stats()}
