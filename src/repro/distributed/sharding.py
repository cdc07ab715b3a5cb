"""Logical-axis sharding rules (MaxText-style) + constraint helpers.

Models declare per-param logical axes (ParamSpec.axes) and annotate
activations with :func:`logical_constraint`.  A :class:`ShardingRules`
context maps logical names -> mesh axes; the same model definition then
runs on the production (pod, data, model) mesh, a single-pod mesh, or an
unsharded CPU smoke test (no context active -> constraints are no-ops).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh axis (or tuple of mesh axes)
TRAIN_RULES: dict[str, Any] = {
    "batch": ("pod", "data"),
    "embed": ("pod", "data"),     # FSDP: params sharded over the data axes
    "embed_out": None,
    "qkv": "model",               # TP over fused head*head_dim features
    "kv": "model",
    "heads": "model",
    "mlp": "model",
    "experts": "model",           # EP
    "expert_mlp": None,           # per-expert hidden: EP already covers it
    "vocab": "model",
    # Sequence parallelism: saved layer activations (the remat carries)
    # shard over "model" as well as batch over "data" — without this an
    # 88-layer 4k x 256 train step saves 88 x (B_loc, S, D) = 217 GB/dev.
    "seq": "model",
    "seq_out": None,            # logits seq dim (vocab already takes "model")
    "tokens": ("pod", "data"),  # flat (B*S) token dim in MoE dispatch
    "kv_seq": None,
    "layers": None,               # scan dim: never sharded
}

SERVE_RULES: dict[str, Any] = {
    **TRAIN_RULES,
    "embed": None,                # no FSDP at serving time: TP only
    "kv_seq": "model",            # split-KV decode: cache seq over model
}

# Pure-FSDP (ZeRO-3) training: NO tensor parallelism — params fully
# sharded over every mesh axis and all-gathered just-in-time; batch over
# (data, model).  Trades the per-layer TP activation all-reduces for
# param gathers: the winning config when activations >> params traffic
# is false, i.e. large models at moderate sequence length.
TRAIN_RULES_FSDP: dict[str, Any] = {
    **TRAIN_RULES,
    "batch": ("data", "model"),
    "embed": ("pod", "data", "model"),
    "qkv": None, "kv": None, "heads": None, "mlp": None, "vocab": None,
    "experts": "model",           # EP stays: expert weights shard by expert
    "seq": None,
}

RULE_PRESETS = {"tp": TRAIN_RULES, "fsdp": TRAIN_RULES_FSDP,
                "serve": SERVE_RULES}


class ShardingRules:
    def __init__(self, mesh: Mesh, rules: dict[str, Any]):
        self.mesh = mesh
        self.rules = dict(rules)

    def spec(self, axes: Sequence[Optional[str]]) -> P:
        """PartitionSpec for a logical-axes tuple, dropping mesh axes the
        current mesh does not have (e.g. no 'pod' on the single-pod mesh)."""
        parts = []
        for ax in axes:
            m = self.rules.get(ax) if ax else None
            if m is None:
                parts.append(None)
                continue
            ms = (m,) if isinstance(m, str) else tuple(m)
            ms = tuple(a for a in ms if a in self.mesh.axis_names)
            parts.append(ms if len(ms) > 1 else (ms[0] if ms else None))
        return P(*parts)

    def sharding(self, axes: Sequence[Optional[str]]) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(axes))


_ACTIVE = threading.local()


@contextlib.contextmanager
def use_rules(rules: ShardingRules):
    prev = getattr(_ACTIVE, "rules", None)
    _ACTIVE.rules = rules
    try:
        yield rules
    finally:
        _ACTIVE.rules = prev


def active_rules() -> Optional[ShardingRules]:
    return getattr(_ACTIVE, "rules", None)


def logical_constraint(x: jax.Array, axes: Sequence[Optional[str]]) -> jax.Array:
    """with_sharding_constraint by logical axes; no-op without a context."""
    rules = active_rules()
    if rules is None:
        return x
    return jax.lax.with_sharding_constraint(x, rules.sharding(axes))


def tree_pspecs(axes_tree: Any, rules: ShardingRules) -> Any:
    """Map a param-axes tree to PartitionSpecs."""
    return jax.tree.map(
        lambda axes: rules.spec(axes), axes_tree,
        is_leaf=lambda x: isinstance(x, tuple))


def tree_shardings(axes_tree: Any, rules: ShardingRules) -> Any:
    return jax.tree.map(
        lambda axes: rules.sharding(axes), axes_tree,
        is_leaf=lambda x: isinstance(x, tuple))
