"""Logical-axis partitioning (hand-rolled; no flax dependency).

Every parameter / activation in :mod:`repro.models` is annotated with a
tuple of *logical* axis names (e.g. ``("layers", "embed", "q_heads")``).
:class:`AxisRules` maps logical names to mesh axes; the same model code
then runs on any mesh — single device (all rules resolve to None), the
single-pod (16, 16) ``("data", "model")`` mesh, or the multi-pod
(2, 16, 16) ``("pod", "data", "model")`` mesh.

Sharding strategy (see DESIGN.md §5):

* tensor-parallel dims (heads / ffn / vocab / experts) -> ``"model"``
* FSDP: the ``"embed"`` dim of weight matrices -> ``("pod", "data")``
  so parameters and optimizer states are fully sharded (ZeRO-3).
* batch -> ``("pod", "data")``; sequence (SP, long-context) -> ``"data"``.

Rules silently drop mesh axes that are absent from the mesh, so the same
rule table serves both single-pod and multi-pod meshes.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "AxisRules",
    "DEFAULT_RULES",
    "LONG_CONTEXT_RULES",
    "logical_sharding",
    "shard_pytree_spec",
    "with_logical_constraint",
    "mesh_axis_sizes",
]


MeshAxes = tuple[str, ...]


@dataclasses.dataclass(frozen=True)
class AxisRules:
    """Mapping logical axis name -> mesh axis (or tuple of mesh axes)."""

    rules: Mapping[str, str | MeshAxes | None]

    def resolve(self, logical: Sequence[str | None], mesh: Mesh) -> P:
        """PartitionSpec for a logical shape annotation on a given mesh.

        Mesh axes not present in ``mesh`` are dropped; a mesh axis may be
        used by at most one dim (first dim wins; later dims replicate),
        mirroring GSPMD validity requirements.
        """
        used: set[str] = set()
        out: list[Any] = []
        for name in logical:
            spec = self.rules.get(name) if name is not None else None
            if spec is None:
                out.append(None)
                continue
            axes = (spec,) if isinstance(spec, str) else tuple(spec)
            axes = tuple(a for a in axes if a in mesh.axis_names and a not in used)
            used.update(axes)
            if not axes:
                out.append(None)
            elif len(axes) == 1:
                out.append(axes[0])
            else:
                out.append(axes)
        # Trim trailing Nones (cosmetic; PartitionSpec semantics identical).
        while out and out[-1] is None:
            out.pop()
        return P(*out)

    def replace(self, **updates: str | MeshAxes | None) -> "AxisRules":
        merged = dict(self.rules)
        merged.update(updates)
        return AxisRules(merged)


#: Baseline rules: FSDP over (pod, data) + TP over model.
DEFAULT_RULES = AxisRules(
    {
        # -- parameter axes -------------------------------------------------
        "embed": ("pod", "data"),  # FSDP shard dim of every weight matrix
        "q_heads": "model",
        "kv_heads": None,  # kv_heads (8) < model axis (16): replicate
        "head_dim": None,
        "mlp": "model",
        "vocab": "model",
        "experts": "model",  # expert parallelism
        "expert_mlp": None,
        "ssm_heads": "model",
        "ssm_state": None,
        "conv_dim": "model",
        "layers": None,  # scan axis, never sharded
        # -- activation axes ------------------------------------------------
        "batch": ("pod", "data"),
        "seq": None,
        "kv_seq": None,
        "act_embed": None,
        "act_heads": "model",
        "act_mlp": "model",
        "act_vocab": "model",
    }
)

#: Long-context (batch=1) rules: sequence parallelism over "data".
LONG_CONTEXT_RULES = DEFAULT_RULES.replace(
    batch=None,
    seq="data",
    kv_seq="data",
)


def logical_sharding(
    logical: Sequence[str | None], mesh: Mesh, rules: AxisRules = DEFAULT_RULES
) -> NamedSharding:
    return NamedSharding(mesh, rules.resolve(logical, mesh))


def shard_pytree_spec(
    logical_tree: Any, mesh: Mesh, rules: AxisRules = DEFAULT_RULES
) -> Any:
    """Map a pytree of logical-axis tuples to a pytree of NamedShardings."""
    return jax.tree.map(
        lambda logical: logical_sharding(logical, mesh, rules),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple),
    )


def with_logical_constraint(
    x: jax.Array, logical: Sequence[str | None], rules: AxisRules, mesh: Mesh | None
) -> jax.Array:
    """`lax.with_sharding_constraint` by logical names; no-op off-mesh.

    Inside jit we can't query the ambient mesh, so callers thread the mesh
    (models receive it via ShardingCtx).  mesh=None disables constraints
    (single-device smoke tests).
    """
    if mesh is None or mesh.empty:
        return x
    return jax.lax.with_sharding_constraint(x, logical_sharding(logical, mesh, rules))


@dataclasses.dataclass(frozen=True)
class ShardingCtx:
    """Threaded through model code: mesh + active rule table.

    ``none()`` gives the no-constraint context used by unit tests.
    """

    mesh: Mesh | None
    rules: AxisRules = DEFAULT_RULES

    @staticmethod
    def none() -> "ShardingCtx":
        return ShardingCtx(mesh=None)

    def constrain(self, x: jax.Array, logical: Sequence[str | None]) -> jax.Array:
        return with_logical_constraint(x, logical, self.rules, self.mesh)

    def sharding(self, logical: Sequence[str | None]) -> NamedSharding | None:
        if self.mesh is None:
            return None
        return logical_sharding(logical, self.mesh, self.rules)


def mesh_axis_sizes(mesh: Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, np.asarray(mesh.devices).shape))


def rules_for(
    cfg,
    *,
    long_context: bool = False,
    decode_batch: bool = False,
    model_axis: int = 16,
) -> AxisRules:
    """Per-architecture sharding rules (see DESIGN.md §5).

    * MoE with few experts (< model axis, e.g. Mixtral's 8): shard the
      expert FFN dim over "model" (TP-within-expert) instead of the expert
      axis — avoids GSPMD padding 8 experts onto 16 shards.
    * MoE with many experts (Kimi 384, Jamba 16): expert parallelism
      (experts over "model"), expert FFN dim replicated within a shard.
    * long_context (batch=1 decode): sequence parallelism — batch
      unsharded, (kv_)seq over "data".
    * decode_batch: KV-cache-resident serving (decode_32k) — the request
      batch shards over ("pod", "model") and the cache sequence over
      "data", so the cache is sharded over the whole mesh.  GSPMD
      decomposes the masked softmax over the sharded kv_seq into partial
      reductions + all-reduces (flash-decode by propagation).  This takes
      a decode_32k KV cache from 40 GiB/chip (batch over data only) to
      ~2.7 GiB/chip.
    """
    rules = LONG_CONTEXT_RULES if long_context else DEFAULT_RULES
    n_experts = getattr(cfg, "n_experts", 0)
    mode = getattr(cfg, "moe_ep", "auto")
    tp_experts = mode == "tp" or (mode == "auto" and 0 < n_experts < model_axis)
    if n_experts and tp_experts:
        rules = rules.replace(experts=None, expert_mlp="model")
    if decode_batch and not long_context:
        rules = rules.replace(batch=("pod", "model"), kv_seq="data")
    return rules


def serving_weight_rules(rules: AxisRules) -> AxisRules:
    """Serving layout (§Perf hillclimb A): TP-static weights + seq-sharded cache.

    The baseline decode layout FSDP-shards weights (embed over data) and
    batch over (pod, model): every decode step must all-gather weights
    over "data" AND reshard activations between the batch-parallel and
    head-parallel GEMM layouts — decode becomes collective-bound (75 GB
    of all-gather per token on granite, §Roofline).

    This layout instead keeps the big tensors static:
      * weights: embed replicated, heads/ffn/vocab over "model" (pure TP;
        per-chip weight bytes = params·2B/16 — fits every non-1T arch);
      * KV cache: batch over ("pod","data"), kv_seq over "model";
      * per-step collectives are then only the small activation psums
        (attention/MLP TP reductions and the sharded-softmax stats).
    """
    return rules.replace(embed=None, batch=("pod", "data"), kv_seq="model")
