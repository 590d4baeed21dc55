"""Quickstart: Fractal partitioning + block-parallel point ops in 60 lines.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache, core
from repro.core import ref

compile_cache.enable()

# A clustered scene: two objects + clutter (the distribution Fractal's
# shape-aware splits exploit).
rng = np.random.default_rng(0)
pts = jnp.asarray(np.concatenate([
    rng.normal([0, 0, 0], 0.3, (2000, 3)),
    rng.normal([3, 1, 0], 0.5, (1500, 3)),
    rng.uniform(-1, 4, (596, 3)),
]).astype(np.float32))
n = pts.shape[0]

# 1. Fractal: shape-aware, sorter-free partitioning (paper Alg. 1).
part = jax.jit(lambda p: core.partition(p, th=256))(pts)
print(f"partitioned {n} points -> {int(part.num_leaves)} blocks "
      f"(max {int(part.max_leaf_vsize)} pts <= th=256), "
      f"{int(part.traversals)} traversals, {int(part.sort_passes)} sorts")

# 2. Block-wise FPS: one fixed rate, fully parallel across blocks.
samp = jax.jit(lambda p: core.blockwise_fps(
    core.partition(p, th=256), rate=0.25, k_out=n // 4, bs=256))(pts)
print(f"sampled {int(samp.valid.sum())}/{n // 4} points block-wise")

# 3. Block-wise ball query: each center searches its parent window only.
nb = jax.jit(lambda p: core.blockwise_ball_query(
    core.partition(p, th=256),
    core.blockwise_fps(core.partition(p, th=256), rate=0.25,
                       k_out=n // 4, bs=256),
    radius=0.3, num=16, w=512))(pts)
print(f"grouping: mean {float(jnp.mean(nb.cnt[samp.valid])):.1f} "
      f"in-radius neighbors per center")

# 4. Compare against the global O(n^2) baseline (PointAcc-style).
sval = np.asarray(samp.valid)
centers = np.asarray(part.coords)[np.asarray(samp.idx)[sval]]
g_idx, g_cnt = ref.ball_query(part.coords, part.valid,
                              jnp.asarray(centers),
                              jnp.ones(len(centers), bool), 0.3, 16)
g_idx, g_cnt = np.asarray(g_idx), np.asarray(g_cnt)
b_idx, b_msk = np.asarray(nb.idx)[sval], np.asarray(nb.mask)[sval]
recalls = [len(set(g_idx[i][:min(g_cnt[i], 16)]) & set(b_idx[i][b_msk[i]]))
           / max(min(g_cnt[i], 16), 1) for i in range(len(centers))]
print(f"block-wise neighbor recall vs global search: "
      f"{np.mean(recalls) * 100:.1f}% (paper: accuracy recovered by "
      f"retraining; see benchmarks/accuracy.py)")
