"""LM serving launcher: continuous batched greedy decoding.

Prefill once per request batch, then step the decode loop with the KV /
recurrent-state caches (the same code path the decode_* dry-run cells
compile for the production mesh).

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
      --batch 2 --prompt-len 16 --max-new 8
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import compile_cache, configs
from repro.dist import elastic, logical
from repro.lm import model as M


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m",
                    choices=sorted(configs.ARCHS))
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--model-axis", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compile_cache.enable()

    cfg = configs.lm_reduced(args.arch)
    if cfg.encoder_layers:
        raise SystemExit("enc-dec serving demo: use examples/ drivers")
    mesh = elastic.make_mesh(model_axis=args.model_axis)
    params, axes = M.init(jax.random.PRNGKey(args.seed), cfg)
    params = jax.device_put(params, logical.fit_specs(
        logical.param_specs(axes, mesh, logical.RULES_V0), params, mesh))
    max_len = args.prompt_len + args.max_new
    toks = jax.random.randint(jax.random.PRNGKey(1),
                              (args.batch, args.prompt_len), 0, cfg.vocab)

    with logical.logical_rules(mesh, logical.RULES_V0):
        prefill = jax.jit(lambda p, t: M.prefill(p, cfg, tokens=t,
                                                 max_len=max_len))
        decode = jax.jit(lambda p, t, c, pos: M.decode_step(p, cfg, t, c,
                                                            pos))
        t0 = time.monotonic()
        logits, cache = prefill(params, toks)
        jax.block_until_ready(logits)
        t_prefill = time.monotonic() - t0

        out = []
        nxt = jnp.argmax(logits, -1)
        t0 = time.monotonic()
        for i in range(args.max_new):
            out.append(nxt)
            logits, cache = decode(params, nxt, cache,
                                   jnp.int32(args.prompt_len + i))
            nxt = jnp.argmax(logits, -1)
        jax.block_until_ready(nxt)
        t_decode = time.monotonic() - t0

    gen = jnp.concatenate(out, axis=1)
    print(f"[serve] {cfg.name}: prefill {args.prompt_len} toks in "
          f"{t_prefill*1e3:.0f} ms; {args.max_new} decode steps in "
          f"{t_decode*1e3:.0f} ms "
          f"({args.max_new * args.batch / max(t_decode, 1e-9):.1f} tok/s)")
    print(f"[serve] generated ids: {gen.tolist()}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
