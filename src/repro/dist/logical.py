"""Logical axis -> mesh axis sharding rules (MaxText-style).

Every tensor dimension in the model code is named with a *logical* axis
("batch", "ff", "blocks", ...) — once, where the tensor is created.  A
rules dict maps logical names to physical mesh axes; ``logical_rules``
activates (mesh, rules) for a region of code, and ``lc`` applies the
resulting sharding constraint to a value.  Swapping the parallelism
strategy (see launch/perf.py variants) is then a rules edit, not a model
edit.

Resolution semantics (flax.linen.partitioning-style):

* a rule value may be a single mesh axis (``"model"``), a tuple of mesh
  axes (``("pod", "data")``), or ``None`` (replicate);
* mesh axes absent from the current mesh are dropped (the same rules file
  serves the 512-chip two-pod mesh and the 8-device host mesh);
* within one spec each mesh axis is used at most once.  Conflicts are
  resolved by *rule priority* — the order of keys in the rules dict — so
  e.g. ``seq_shard`` (sequence-parallel v0 baseline) beats ``heads`` when
  both map to ``model`` and both appear on one tensor.

Outside a ``logical_rules`` context everything is a no-op: ``lc`` returns
its input unchanged, so single-process tests run the exact sharded code.
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

# v0 baseline (docs/DESIGN.md §6): clouds/batch -> data axes, fractal leaves and
# tensor-parallel dims -> model, params FSDP-sharded over data.  Key order
# is rule priority (earlier wins a contested mesh axis).
RULES_V0 = {
    # activations
    "batch": ("pod", "data"),     # data parallelism (pods x hosts)
    "seq_shard": "model",         # sequence-parallel attention (train/prefill)
    "kv_seq": "model",            # decode KV-cache sequence
    "blocks": "model",            # fractal leaves -> chips (paper §IV-B)
    "expert_cap": "model",        # MoE capacity rows (TP)
    # parameters
    "experts": "data",            # expert parallelism
    "embed_fsdp": "data",         # FSDP shard dim of weight matrices
    "ff": "model",                # MLP hidden / fused head dim (TP)
    "vocab": "model",             # embedding / logits vocab dim
    "heads": "model",             # attention heads (perf variants)
    "ssm_heads": "model",         # mamba / SSD heads
    # replicated-by-default names (kept explicit so rules_with can flip them)
    "embed": None,                # activation d_model dim
    "points": None,               # flat per-point tensors
    "layers": None,               # stacked scan/cache leading dim
}


def rules_with(**overrides):
    """RULES_V0 with per-variant overrides (``ff=None``, ``points="model"``,
    ``batch=("pod", "data", "model")``, ...)."""
    rules = dict(RULES_V0)
    rules.update(overrides)
    return rules


class _Ctx:
    """An active (mesh, rules) binding."""

    __slots__ = ("mesh", "rules", "mesh_sizes")

    def __init__(self, mesh, rules):
        self.mesh = mesh
        self.rules = dict(rules)
        self.mesh_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))


_LOCAL = threading.local()


def _stack():
    if not hasattr(_LOCAL, "stack"):
        _LOCAL.stack = []
    return _LOCAL.stack


def current() -> _Ctx | None:
    """The innermost active binding; None outside any, or when
    ``suspended``."""
    stack = _stack()
    return stack[-1] if stack else None


@contextlib.contextmanager
def logical_rules(mesh, rules):
    """Activate (mesh, rules) for ``lc`` / ``spec`` / ``axis_size``.

    Jitted functions must be *traced* inside the context (call them inside
    the ``with`` block); the constraints are baked into the jaxpr.

    ``lc`` places values with ``with_sharding_constraint``, which only
    accepts Auto mesh axes; a mesh with other axis types is refused here
    rather than deep inside a trace."""
    other = [n for n, t in zip(mesh.axis_names, mesh.axis_types)
             if t != AxisType.Auto]
    if other:
        raise ValueError(
            f"logical_rules needs a mesh whose axes are all Auto, as "
            f"dist.elastic.make_mesh builds (jax.make_mesh(..., axis_types="
            f"(AxisType.Auto,) * k)); axes {other} are not")
    stack = _stack()
    stack.append(_Ctx(mesh, rules))
    try:
        yield stack[-1]
    finally:
        stack.pop()


@contextlib.contextmanager
def suspended():
    """Run without the active (mesh, rules): ``lc`` / ``spec`` /
    ``axis_size`` act as outside any context.  For the body of a
    ``shard_map``, where every mesh axis is manual and there is nothing
    left for a sharding constraint to place."""
    stack = _stack()
    stack.append(None)
    try:
        yield
    finally:
        stack.pop()


def _axis_to_mesh(ctx: _Ctx, axis, used=None):
    """One logical axis -> mesh-axes spec entry (str | tuple | None).

    Preserves the rule's str/tuple form; drops mesh axes absent from the
    mesh or already consumed (``used`` set) in the enclosing spec."""
    if axis is None:
        return None
    rule = ctx.rules.get(axis)
    if rule is None:
        return None
    if isinstance(rule, str):
        if rule in ctx.mesh_sizes and (used is None or rule not in used):
            if used is not None:
                used.add(rule)
            return rule
        return None
    kept = tuple(a for a in rule
                 if a in ctx.mesh_sizes and (used is None or a not in used))
    if not kept:
        return None
    if used is not None:
        used.update(kept)
    return kept


def _spec_entries(ctx: _Ctx, axes):
    """All dims of one tensor -> spec entries, with priority resolution.

    Dims are assigned in rule-priority order (position of the logical name
    in the rules dict), so when two dims contend for one mesh axis the
    higher-priority logical axis wins and the other replicates."""
    prio = {name: i for i, name in enumerate(ctx.rules)}
    order = sorted(range(len(axes)),
                   key=lambda d: prio.get(axes[d], len(prio)))
    used: set = set()
    entries = [None] * len(axes)
    for d in order:
        entries[d] = _axis_to_mesh(ctx, axes[d], used)
    return entries


def spec(axes) -> P:
    """Logical axes tuple -> PartitionSpec under the active context
    (``P()`` when no context is active)."""
    ctx = current()
    if ctx is None:
        return P()
    return P(*_spec_entries(ctx, tuple(axes)))


def axis_size(name: str) -> int:
    """Product of the mesh-axis sizes a logical axis maps to (1 outside a
    context, or when the axis replicates)."""
    ctx = current()
    if ctx is None:
        return 1
    entry = _axis_to_mesh(ctx, name)
    if entry is None:
        return 1
    axes = (entry,) if isinstance(entry, str) else entry
    size = 1
    for a in axes:
        size *= ctx.mesh_sizes[a]
    return size


def lc(x, *axes):
    """Logical sharding constraint: ``lc(x, "batch", None, "ff")``.

    No-op (returns ``x``) outside a ``logical_rules`` context; inside one,
    applies ``with_sharding_constraint`` with the resolved NamedSharding."""
    ctx = current()
    if ctx is None:
        return x
    if len(axes) != x.ndim:
        raise ValueError(f"lc: {len(axes)} axis names for rank-{x.ndim} "
                         f"value {getattr(x, 'shape', ())}: {axes}")
    entries = _spec_entries(ctx, axes)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, P(*entries)))


def _entry_size(mesh_sizes, entry) -> int:
    if entry is None:
        return 1
    axes = (entry,) if isinstance(entry, str) else entry
    size = 1
    for a in axes:
        size *= mesh_sizes[a]
    return size


def entry_size(mesh, entry) -> int:
    """Device count along one PartitionSpec entry (str | tuple | None)."""
    return _entry_size(dict(zip(mesh.axis_names, mesh.devices.shape)),
                       entry)


def fit_specs(shard_tree, shape_tree, mesh):
    """Null out spec entries whose device count does not divide the dim.

    ``device_put`` and jit argument shardings must divide evenly; reduced
    configs (odd widths) and small batches (batch=1 decode) routinely
    don't, so launchers fit the derived specs against the actual shapes."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def one(sh, val):
        new = []
        for dim, entry in enumerate(sh.spec):
            if entry is not None and val.shape[dim] % _entry_size(sizes,
                                                                  entry):
                entry = None
            new.append(entry)
        return NamedSharding(mesh, P(*new))

    return jax.tree.map(one, shard_tree, shape_tree)


def _is_axes_leaf(node) -> bool:
    """Leaves of a logical-axes tree: None, or a tuple of axis names."""
    return node is None or (
        isinstance(node, tuple)
        and all(e is None or isinstance(e, str) for e in node))


def param_specs(axes_tree, mesh, rules=None):
    """Logical-axes tree -> NamedSharding tree for ``jax.device_put`` /
    ``jit`` in_shardings.  ``None`` leaves replicate (``P()``); mesh axes
    absent from ``mesh`` are dropped."""
    ctx = _Ctx(mesh, RULES_V0 if rules is None else rules)

    def one(axes):
        if axes is None:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(*_spec_entries(ctx, axes)))

    return jax.tree.map(one, axes_tree, is_leaf=_is_axes_leaf)
