"""Logical-axis → mesh-axis sharding rules (MaxText-style, divisibility-safe).

Every parameter/activation dimension carries a *logical* name; rules map the
name to mesh axes.  ``logical_to_spec`` drops any assignment that does not
divide the dimension (jax requires divisible input shardings), so a single
rule table serves every architecture — e.g. `heads` lands on `model` only
after TP padding made it divisible, `vocab` always divides by construction.

Default placement (single-pod mesh ``(data=16, model=16)``; multi-pod adds a
leading ``pod`` axis used as an extra data dimension):

  batch      → (pod, data)        activations' leading dim
  fsdp       → data               parameter ZeRO-3 sharding dim
  heads      → model              TP over (padded) query heads
  kv_heads   → model (if divides) else replicated
  d_ff       → model              TP over MLP hidden
  vocab      → model              TP over the (padded) vocabulary
  experts    → model              expert parallelism
  seq_kv     → model              KV-cache sequence dim (decode memory / SP)
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class AxisRules:
    rules: dict[str, tuple[str, ...]] = field(default_factory=dict)
    mesh_axis_sizes: dict[str, int] = field(default_factory=dict)
    mesh: object = None  # the jax Mesh (needed for shard_map sub-regions)

    def with_overrides(self, **kw) -> "AxisRules":
        r = dict(self.rules)
        for k, v in kw.items():
            r[k] = tuple(v) if v else ()
        return AxisRules(r, self.mesh_axis_sizes, self.mesh)


def default_rules(mesh) -> AxisRules:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    batch_axes = tuple(a for a in ("pod", "data") if a in sizes)
    return AxisRules(
        mesh=mesh,
        rules={
            "batch": batch_axes,
            # ZeRO-3 over every data-parallel axis (incl. pod): gradient
            # reductions lower to reduce-scatters into the shard instead of
            # full-tensor all-reduces, params all-gather bf16 on use.
            "fsdp": batch_axes,
            "heads": ("model",),
            "kv_heads": ("model",),
            "d_ff": ("model",),
            "vocab": ("model",),
            "embed_d": ("model",),
            "experts": ("model",),
            "seq": (),
            "seq_kv": ("model",),
            "d_model": (),
            "head_dim": (),
            "ssm_inner": ("model",),
            "ssm_state": (),
            "rnn_width": ("model",),
            "stack": (),          # scan-over-layers leading dim
        },
        mesh_axis_sizes=sizes,
    )


DEFAULT_RULES = default_rules  # alias: call with a mesh


def logical_to_spec(logical: tuple[str | None, ...], rules: AxisRules,
                    dims: tuple[int, ...] | None = None) -> P:
    """Map logical dim names to a PartitionSpec, dropping non-divisible axes."""
    out = []
    used: set[str] = set()
    for i, name in enumerate(logical):
        if name is None:
            out.append(None)
            continue
        axes = tuple(a for a in rules.rules.get(name, ()) if a not in used)
        if dims is not None and axes:
            size = 1
            for a in axes:
                size *= rules.mesh_axis_sizes.get(a, 1)
            if size == 0 or dims[i] % size != 0:
                axes = ()
        used.update(axes)
        if not axes:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(tuple(axes))
    return P(*out)


def shard_constraint(x, logical: tuple[str | None, ...], rules: AxisRules):
    """with_sharding_constraint by logical names (no-op outside a mesh ctx)."""
    try:
        spec = logical_to_spec(logical, rules, tuple(x.shape))
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        return x


def spec_tree_for_params(logical_tree, rules: AxisRules, shape_tree):
    """Map a pytree of logical-name tuples + shapes to PartitionSpecs."""
    return jax.tree.map(
        lambda logical, shaped: logical_to_spec(tuple(logical), rules, tuple(shaped.shape)),
        logical_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x),
    )


def named_sharding_tree(spec_tree, mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
