"""Distributed training launcher.

Wires mesh selection (elastic), logical sharding rules, the jitted train
step, the fault-tolerant loop (checkpoint/restart, straggler monitor,
optional gradient compression) and the resumable synthetic data pipeline.

On this CPU container it runs reduced configs on host devices; on a real
pod the same entrypoint runs the full config (the dry-run proves those
compile). Examples:

  # LM pretraining smoke on whatever devices exist:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --steps 50 --batch 4 --seq 64 --ckpt /tmp/ck

  # resume after a crash: rerun the same command (restores latest step)
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compile_cache, configs
from repro.dist import elastic, logical
from repro.lm import model as M
from repro.lm import steps as steps_lib
from repro.train import loop as loop_lib
from repro.train import optimizer as opt_lib


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m",
                    choices=sorted(configs.ARCHS))
    ap.add_argument("--full-config", action="store_true",
                    help="use the full (pod-scale) config instead of the "
                         "reduced one")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--compression", default="none",
                    choices=["none", "bf16", "int8"])
    ap.add_argument("--model-axis", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compile_cache.enable()

    cfg = (configs.lm_config(args.arch) if args.full_config
           else configs.lm_reduced(args.arch))
    mesh = elastic.make_mesh(model_axis=args.model_axis)
    print(f"[train] mesh {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"({mesh.devices.size} devices), arch {cfg.name}")
    rules = logical.RULES_V0
    opt_cfg = opt_lib.OptConfig(lr=args.lr, warmup=min(10, args.steps // 5),
                                total_steps=args.steps)

    def init_params():
        params, axes = M.init(jax.random.PRNGKey(args.seed), cfg)
        specs = logical.fit_specs(
            logical.param_specs(axes, mesh, rules), params, mesh)
        return jax.device_put(params, specs)

    b_sh = NamedSharding(mesh, P(tuple(
        a for a in ("pod", "data") if a in mesh.axis_names), ))

    def next_batch(step):
        key = jax.random.fold_in(jax.random.PRNGKey(args.seed + 7), step)
        toks = jax.random.randint(key, (args.batch, args.seq), 0, cfg.vocab)
        labels = (toks * 7 + jnp.arange(args.seq)[None, :]) % cfg.vocab
        batch = {"labels": labels}
        if cfg.encoder_layers or cfg.frontend == "embeddings":
            batch["frames"] = jax.random.normal(
                key, (args.batch, args.seq, cfg.d_model)) * 0.1
            if cfg.encoder_layers:
                batch["dec_tokens"] = toks
        else:
            batch["tokens"] = toks
        sh = {k: b_sh if v.ndim == 2 else NamedSharding(
            mesh, P(b_sh.spec[0], None, None))
            for k, v in batch.items()}
        return jax.device_put(batch, logical.fit_specs(sh, batch, mesh))

    base = steps_lib.make_train_step(cfg, opt_cfg,
                                     microbatch=args.microbatch)
    jitted = jax.jit(base)
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, b: steps_lib.loss_fn(p, cfg, b)[0]))

    def train_step(params, opt_state, batch, return_grads=False):
        with logical.logical_rules(mesh, rules):
            if return_grads:
                loss, grads = grad_fn(params, batch)
                return grads, {"loss": loss}
            return jitted(params, opt_state, batch)

    loop_cfg = loop_lib.LoopConfig(
        total_steps=args.steps, ckpt_dir=args.ckpt,
        ckpt_every=args.ckpt_every, log_every=10,
        grad_compression=args.compression, seed=args.seed)
    params, _, info = loop_lib.run(
        loop_cfg, init_params=init_params, train_step=train_step,
        next_batch=next_batch, opt_cfg=opt_cfg)
    h = info["history"]
    if h:
        print(f"[train] done: loss {h[0]['loss']:.4f} -> "
              f"{h[-1]['loss']:.4f}; {info['monitor']}")
    else:
        print("[train] nothing to do: checkpoint already at "
              f"step >= {args.steps}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
