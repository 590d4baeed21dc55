"""LM-substrate smoke driver: pretrain a reduced assigned-arch config on a
synthetic token stream with the full fault-tolerant loop (checkpoint/
restart, straggler monitor, optional gradient compression).

Run:  PYTHONPATH=src python examples/lm_pretrain_smoke.py \
          [--arch smollm-135m] [--steps 60] [--compression int8]
"""
import argparse

import jax
import jax.numpy as jnp

from repro import compile_cache, configs
from repro.lm import model as M
from repro.lm import steps as steps_lib
from repro.train import loop as loop_lib
from repro.train import optimizer as opt_lib


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m",
                    choices=sorted(configs.ARCHS))
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt", default="/tmp/repro_lm_ckpt")
    ap.add_argument("--compression", default="none",
                    choices=["none", "bf16", "int8"])
    args = ap.parse_args()
    compile_cache.enable()

    cfg = configs.lm_reduced(args.arch)
    opt_cfg = opt_lib.OptConfig(lr=1e-3, warmup=10,
                                total_steps=args.steps)

    def init_params():
        return M.init(jax.random.PRNGKey(0), cfg)[0]

    def next_batch(step):
        # synthetic structured stream: next-token = (token*7 + pos) % vocab
        key = jax.random.fold_in(jax.random.PRNGKey(42), step)
        toks = jax.random.randint(key, (args.batch, args.seq), 0, cfg.vocab)
        labels = (toks * 7 + jnp.arange(args.seq)[None, :]) % cfg.vocab
        batch = {"labels": labels}
        if cfg.encoder_layers:
            batch["frames"] = jax.random.normal(
                key, (args.batch, args.seq, cfg.d_model)) * 0.1
            batch["dec_tokens"] = toks
        elif cfg.frontend == "embeddings":
            batch["frames"] = jax.random.normal(
                key, (args.batch, args.seq, cfg.d_model)) * 0.1
        else:
            batch["tokens"] = toks
        return batch

    base_step = steps_lib.make_train_step(cfg, opt_cfg)
    jit_step = jax.jit(base_step)

    def train_step(params, opt_state, batch, return_grads=False):
        if return_grads:
            def loss_f(p):
                return steps_lib.loss_fn(p, cfg, batch)[0]
            loss, grads = jax.value_and_grad(loss_f)(params)
            return grads, {"loss": loss}
        return jit_step(params, opt_state, batch)

    loop_cfg = loop_lib.LoopConfig(
        total_steps=args.steps, ckpt_dir=args.ckpt, ckpt_every=20,
        log_every=10, grad_compression=args.compression)
    params, _, info = loop_lib.run(
        loop_cfg, init_params=init_params, train_step=train_step,
        next_batch=next_batch, opt_cfg=opt_cfg)
    h = info["history"]
    print(f"[{args.arch}] loss {h[0]['loss']:.3f} -> {h[-1]['loss']:.3f} "
          f"over {len(h)} steps; monitor={info['monitor']}")


if __name__ == "__main__":
    main()
