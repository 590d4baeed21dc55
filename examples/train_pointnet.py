"""End-to-end driver: train a PointNet++ classifier on synthetic shapes with
FractalCloud block-parallel point ops, with checkpoint/restart + straggler
monitoring (the full training substrate).

Run:  PYTHONPATH=src python examples/train_pointnet.py \
          [--steps 300] [--point-ops bppo|global] [--ckpt /tmp/pnn_ckpt]

Compare final accuracy across --point-ops to reproduce the paper's
accuracy-preservation claim (Fig. 14) at laptop scale.
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.data import synthetic
from repro.models import pnn
from repro.train import checkpoint as ckpt_lib
from repro.train import optimizer as opt_lib
from repro.train.monitor import StepMonitor


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--n-points", type=int, default=512)
    ap.add_argument("--point-ops", default="bppo",
                    choices=["bppo", "global"])
    ap.add_argument("--th", type=int, default=64)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    compile_cache.enable()

    cfg = pnn.pointnet2_cls(n=args.n_points, point_ops=args.point_ops,
                            th=args.th)
    params = pnn.init(jax.random.PRNGKey(args.seed), cfg)
    opt_cfg = opt_lib.OptConfig(lr=2e-3, warmup=20,
                                total_steps=args.steps, weight_decay=1e-4)
    opt_state = opt_lib.init(params)
    start = 0
    saver = ckpt_lib.AsyncCheckpointer(args.ckpt) if args.ckpt else None
    if saver and (last := ckpt_lib.latest_step(args.ckpt)) is not None:
        state, manifest = ckpt_lib.restore(
            args.ckpt, last, {"params": params, "opt": opt_state})
        params, opt_state = state["params"], state["opt"]
        start = manifest["extra"]["next_step"]
        print(f"resumed from step {last}")

    @jax.jit
    def train_step(params, opt_state, pts, labels):
        def loss_f(p):
            logits = jax.vmap(lambda c: pnn.apply(p, cfg, c))(pts)
            ll = jax.nn.log_softmax(logits)
            return -jnp.mean(jnp.take_along_axis(ll, labels[:, None], 1))

        loss, grads = jax.value_and_grad(loss_f)(params)
        params, opt_state, om = opt_lib.update(opt_cfg, grads, opt_state,
                                               params)
        return params, opt_state, loss

    @jax.jit
    def eval_acc(params, pts, labels):
        logits = jax.vmap(lambda c: pnn.apply(params, cfg, c))(pts)
        return jnp.mean(jnp.argmax(logits, -1) == labels)

    monitor = StepMonitor()
    for step in range(start, args.steps):
        pts, labels = synthetic.classification_batch(
            args.seed, step, args.batch, args.n_points)
        t0 = time.time()
        params, opt_state, loss = train_step(params, opt_state, pts, labels)
        loss.block_until_ready()
        straggler = monitor.record(step, time.time() - t0)
        if step % 25 == 0:
            accs = [float(eval_acc(params, *synthetic.classification_batch(
                args.seed + 999, s, args.batch, args.n_points)))
                for s in range(4)]
            print(f"step {step:4d} loss {float(loss):.4f} "
                  f"eval_acc {np.mean(accs):.3f}"
                  f"{' [straggler]' if straggler else ''}")
        if saver and step and step % 100 == 0:
            saver.save(step, {"params": params, "opt": opt_state},
                       extra={"next_step": step + 1})

    accs = [float(eval_acc(params, *synthetic.classification_batch(
        args.seed + 999, s, args.batch, args.n_points))) for s in range(8)]
    print(f"FINAL [{args.point_ops}] accuracy: {np.mean(accs):.3f} "
          f"({monitor.summary()})")
    if saver:
        saver.save(args.steps, {"params": params, "opt": opt_state},
                   extra={"next_step": args.steps})
        saver.wait()


if __name__ == "__main__":
    main()
