"""Distribution tests: logical rules, spec trees, and a real multi-device
jit on host devices (subprocess: device count must be set pre-import)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.dist import elastic, logical

jax.config.update("jax_platform_name", "cpu")


def _auto_mesh():
    """A (1, 1) ("data", "model") mesh with Auto axes, built the way the
    program builds its own (``elastic.make_mesh``)."""
    return elastic.make_mesh(devices=jax.devices()[:1])


class TestLogicalRules:
    def test_spec_mapping(self):
        mesh = _auto_mesh()
        with logical.logical_rules(mesh, logical.RULES_V0):
            assert logical.spec(("batch", None, "ff")) == \
                P(("data",), None, "model")
            assert logical.spec((None, None)) == P(None, None)
        # outside a context: no-op
        assert logical.spec(("batch",)) == P()

    def test_missing_mesh_axis_dropped(self):
        mesh = _auto_mesh()  # no "pod"
        with logical.logical_rules(mesh, logical.RULES_V0):
            # "batch" -> ("pod","data") but pod is absent
            assert logical.spec(("batch",)) == P(("data",),)

    def test_param_specs_tree(self):
        mesh = _auto_mesh()
        axes = {"w": ("embed_fsdp", "ff"), "g": None,
                "nested": {"e": ("experts", None, "ff")}}
        specs = logical.param_specs(axes, mesh)
        assert specs["w"].spec == P("data", "model")
        assert specs["g"].spec == P()
        assert specs["nested"]["e"].spec == P("data", None, "model")

    def test_lc_noop_without_context(self):
        x = jax.numpy.ones((4, 4))
        assert logical.lc(x, "batch", "ff") is x


SUBPROCESS_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import configs
    from repro.dist import logical
    from repro.lm import steps as steps_lib, model as M
    from repro.train import optimizer as opt_lib

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = configs.lm_reduced("gemma2-2b")
    params, axes = M.init(jax.random.PRNGKey(0), cfg)
    p_sh = logical.param_specs(axes, mesh, logical.RULES_V0)
    params = jax.device_put(params, p_sh)
    opt = opt_lib.init(params)
    step = steps_lib.make_train_step(
        cfg, opt_lib.OptConfig(lr=1e-3, warmup=0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}
    b_sh = NamedSharding(mesh, P(("data",), None))
    batch = jax.device_put(batch, {"tokens": b_sh, "labels": b_sh})
    with logical.logical_rules(mesh, logical.RULES_V0):
        jitted = jax.jit(step)
        p1, o1, m1 = jitted(params, opt, batch)
        p2, o2, m2 = jitted(p1, o1, batch)
    print(json.dumps({
        "loss1": float(m1["loss"]), "loss2": float(m2["loss"]),
        "n_dev": len(jax.devices()),
        "sharded": any(len(x.sharding.device_set) > 1
                       for x in jax.tree.leaves(p1)),
    }))
""")


def test_multidevice_train_step_runs():
    """End-to-end SPMD: 8 host devices, (4,2) mesh, real sharded train
    step with the v0 logical rules — loss finite and decreasing."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", SUBPROCESS_PROG],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    assert data["n_dev"] == 8
    assert data["sharded"], "no parameter was actually sharded"
    assert np.isfinite(data["loss1"])
    assert data["loss2"] < data["loss1"]


MOE_A2A_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.dist import logical
    from repro.lm import moe as moe_lib

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    key = jax.random.PRNGKey(0)
    p, _ = moe_lib.moe_init(key, 32, 48, 8, kind="swiglu")
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 32))
    with logical.logical_rules(mesh, logical.RULES_V0):
        # cf high enough that neither global nor per-group capacity drops
        f_g = jax.jit(lambda p, x: moe_lib.moe_apply(
            p, x, n_experts=8, top_k=2, capacity_factor=8.0,
            dispatch="global_sort")[0])
        f_a = jax.jit(lambda p, x: moe_lib.moe_apply(
            p, x, n_experts=8, top_k=2, capacity_factor=8.0,
            dispatch="grouped_a2a")[0])
        yg = f_g(p, x)
        ya = f_a(p, x)
    err = float(jnp.max(jnp.abs(yg - ya)))
    print(json.dumps({"err": err,
                      "scale": float(jnp.max(jnp.abs(yg)))}))
""")


def test_grouped_a2a_moe_matches_global_sort():
    """§Perf variant correctness: grouped all-to-all dispatch == global
    sort dispatch when nothing is dropped, on a real 8-device mesh."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", MOE_A2A_PROG],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    assert data["err"] < 1e-4 * max(data["scale"], 1.0), data


def test_mesh_functions_pure():
    """Importing launch.mesh must not initialize jax device state."""
    import importlib
    import repro.launch.mesh as mesh_mod
    importlib.reload(mesh_mod)  # would fail if module-level jax state
