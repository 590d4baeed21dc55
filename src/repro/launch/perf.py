"""§Perf hillclimb driver: re-run a dry-run cell under named variants and
report the roofline-term deltas (hypothesis -> change -> before/after).

Variants are *structural changes* (sharding rules or config knobs), so a
variant row is directly comparable with the baseline row of the same cell.

Usage:
  python -m repro.launch.perf --cell granite-moe-3b-a800m:train_4k \
      --variants baseline,a2a_moe --out results/perf_granite.json
"""
import argparse
import dataclasses
import json

from repro import configs
from repro.dist import logical


def _moe_override(arch, **kw):
    cfg = configs.lm_config(arch)
    return {"moe": dataclasses.replace(cfg.moe, **kw)}


VARIANTS = {
    # paper/v0 baseline
    "baseline": lambda arch: {},
    # H1: MoE token movement via grouped all-to-all instead of global-sort
    # gathers (dominant collective term on MoE cells).
    "a2a_moe": lambda arch: {
        "cfg_overrides": _moe_override(arch, dispatch="grouped_a2a")},
    # H2: small models should not be tensor-parallel: give the model axis
    # to data parallelism (per-layer collectives vanish; pure DP grads).
    "dp_only": lambda arch: {
        "rules": logical.rules_with(
            batch=("pod", "data", "model"), ff=None, vocab=None,
            seq_shard=None, embed_fsdp=("data", "model"),
            expert_cap=None, heads=None, ssm_heads=None)},
    # H4: larger flash chunk (fewer scan steps, bigger tiles).
    "flash4k": lambda arch: {"cfg_overrides": {"flash_chunk": 4096}},
    # H5: no remat (memory-for-flops trade: removes the recompute pass).
    "no_remat": lambda arch: {"cfg_overrides": {"remat": False}},
    # H2b: dp_only + no remat (memory is plentiful without TP, so stop
    # paying the recompute flops/bytes).
    "dp_no_remat": lambda arch: {
        "rules": VARIANTS["dp_only"](arch)["rules"],
        "cfg_overrides": {"remat": False}},
    # H1b: grouped A2A + microbatch 2 (halves the per-step FSDP param
    # re-gathers that dominate what's left of t_coll).
    "a2a_mb2": lambda arch: {
        "cfg_overrides": _moe_override(arch, dispatch="grouped_a2a"),
        "microbatch": 2},
    # H1c: grouped A2A + microbatch 8.
    "a2a_mb8": lambda arch: {
        "cfg_overrides": _moe_override(arch, dispatch="grouped_a2a"),
        "microbatch": 8},
    # H1d: grouped A2A + bf16 parameters (f32 optimizer states remain):
    # halves FSDP param all-gather wire bytes — the residual t_coll term.
    "a2a_bf16": lambda arch: {
        "cfg_overrides": {**_moe_override(arch, dispatch="grouped_a2a"),
                          "param_dtype": "bfloat16"}},
}


PNN_VARIANTS_PERF = {
    # v0 baseline: clouds -> data, leaves -> model, leaf-chunked ops
    "baseline": {},
    # H-P4: shard the flat per-point tensors over model so the
    # block->flat scatters stop all-reducing.
    "points_sharded": {"rules": logical.rules_with(points="model")},
    # H-P1: shard leaves over ALL chips (clouds replicated): the paper's
    # inter-block parallelism at full pod width.
    "blocks_all": {"rules": logical.rules_with(
        batch=None, blocks=("data", "model"))},
    # H-P2: bigger leaf chunks (fewer scan steps <-> larger live tiles).
    "chunk2k": {"leaf_chunk": 2048},
    # H-P3: paper-baseline global ops (PointAcc-style) for the BPPO
    # speedup comparison at pod scale.
    "global_ops": {"point_ops": "global", "batch": 16},
}


def run_variant(arch, shape, variant, multi_pod=False):
    from repro.launch.dryrun import run_cell
    from repro.launch.pnn_cell import PNN_VARIANTS, run_pnn_cell
    if arch in PNN_VARIANTS:
        spec = dict(PNN_VARIANTS_PERF[variant])
        row = run_pnn_cell(arch, shape, multi_pod=multi_pod, **spec)
    else:
        spec = VARIANTS[variant](arch)
        row = run_cell(arch, shape, multi_pod=multi_pod,
                       rules=spec.get("rules"),
                       cfg_overrides=spec.get("cfg_overrides"),
                       microbatch=spec.get("microbatch"))
    row["variant"] = variant
    return row


def main(argv=None):
    from repro.launch.dryrun import force_host_devices
    force_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True, help="arch:shape")
    ap.add_argument("--variants", default="baseline")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    arch, shape = args.cell.split(":")
    rows = []
    for v in args.variants.split(","):
        try:
            rows.append(run_variant(arch, shape, v, args.multi_pod))
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            rows.append({"arch": arch, "shape": shape, "variant": v,
                         "error": str(e)})
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    for r in rows:
        if "error" in r:
            print(f"{r['variant']}: ERROR {r['error'][:120]}")
            continue
        print(f"{r['variant']:12s} t_comp={r['t_compute_s']*1e3:9.2f}ms "
              f"t_mem={r['t_memory_s']*1e3:9.2f}ms "
              f"t_coll={r['t_collective_s']*1e3:9.2f}ms "
              f"bound={r['bottleneck']:10s} useful={r['usefulness']*100:5.1f}% "
              f"peak={r['mem_per_device']['peak_mb']/1024:6.2f}GB")


if __name__ == "__main__":
    main()
