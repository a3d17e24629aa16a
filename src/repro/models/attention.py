"""Attention modules: GQA self-attention (causal / SWA / bidir), cross
attention, decode against KV caches, and sequence-parallel long-context
decode (distributed flash-decode with log-sum-exp combination)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import ref_attention
from repro.models.config import ModelConfig
from repro.models.init import ParamSpec
from repro.models.layers import rms_norm, rope
from repro.parallel.sharding import ShardingCtx

__all__ = [
    "attn_specs",
    "cross_attn_specs",
    "attn_apply",
    "attn_decode",
    "cross_attn_apply",
    "sp_decode_attention",
]


def attn_specs(cfg: ModelConfig, cross: bool = False) -> dict:
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    specs = {
        "wq": ParamSpec((d, hq, hd), ("embed", "q_heads", "head_dim"), dtype=cfg.pdtype),
        "wk": ParamSpec((d, hkv, hd), ("embed", "kv_heads", "head_dim"), dtype=cfg.pdtype),
        "wv": ParamSpec((d, hkv, hd), ("embed", "kv_heads", "head_dim"), dtype=cfg.pdtype),
        "wo": ParamSpec((hq, hd, d), ("q_heads", "head_dim", "embed"), dtype=cfg.pdtype),
    }
    if cfg.qk_norm:
        specs["q_norm"] = ParamSpec((hd,), (None,), init="ones", dtype=jnp.float32)
        specs["k_norm"] = ParamSpec((hd,), (None,), init="ones", dtype=jnp.float32)
    if cross:
        specs["gate"] = ParamSpec((), (), init="zeros", dtype=jnp.float32)
    return specs


def cross_attn_specs(cfg: ModelConfig) -> dict:
    return attn_specs(cfg, cross=True)


def _project_q(p, x, cfg: ModelConfig, ctx, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    if positions is not None:
        q = rope(q, positions, cfg.rope_theta)
    return ctx.constrain(q, ("batch", "seq", "act_heads", "head_dim"))


def _project_kv(p, x, cfg: ModelConfig, ctx, positions):
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if positions is not None:
        k = rope(k, positions, cfg.rope_theta)
    k = ctx.constrain(k, ("batch", "kv_seq", "kv_heads", "head_dim"))
    v = ctx.constrain(v, ("batch", "kv_seq", "kv_heads", "head_dim"))
    return k, v


def _out_proj(p, o, ctx):
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return ctx.constrain(out, ("batch", "seq", "act_embed"))


def attn_apply(
    p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    ctx: ShardingCtx,
    positions: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
) -> jax.Array:
    """Full-sequence self attention (training / prefill)."""
    q = _project_q(p, x, cfg, ctx, positions)
    k, v = _project_kv(p, x, cfg, ctx, positions)
    o = flash_attention(
        q, k, v, causal=causal, window=window, impl=cfg.attn_impl,
        block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
    )
    return _out_proj(p, o, ctx)


def cross_attn_apply(
    p: dict,
    x: jax.Array,
    memory_kv: tuple[jax.Array, jax.Array],
    cfg: ModelConfig,
    ctx: ShardingCtx,
    *,
    gated: bool = False,
) -> jax.Array:
    """Cross attention against precomputed memory K/V (no rope, no mask)."""
    q = _project_q(p, x, cfg, ctx, positions=None)
    k, v = memory_kv
    o = flash_attention(q, k, v, causal=False, impl=cfg.attn_impl,
                        block_q=cfg.attn_block_q, block_k=cfg.attn_block_k)
    out = _out_proj(p, o, ctx)
    if gated:  # llama-3.2-vision tanh gate, initialized at 0
        out = jnp.tanh(p["gate"]).astype(out.dtype) * out
    return out


def memory_kv(p: dict, memory: jax.Array, cfg: ModelConfig, ctx: ShardingCtx):
    """Precompute cross-attention K/V once per sequence (serving + training)."""
    return _project_kv(p, memory, cfg, ctx, positions=None)


# ---------------------------------------------------------------------------
# Decode (single new token against a cache)
# ---------------------------------------------------------------------------


def attn_decode(
    p: dict,
    x: jax.Array,  # (B, 1, D)
    k_cache: jax.Array,  # (B, S_max, Hkv, hd)
    v_cache: jax.Array,
    pos: jax.Array,  # scalar int32: index of the new token
    cfg: ModelConfig,
    ctx: ShardingCtx,
    *,
    ring: bool = False,
    sp: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-token attention; returns (out, new_k_cache, new_v_cache).

    ``ring=True`` treats the cache as a sliding-window ring buffer of
    width S_max (Mixtral SWA long-decode).  ``sp=True`` uses the
    sequence-parallel distributed decode path (cache sharded over "data").
    """
    positions = jnp.full((x.shape[0], 1), pos, dtype=jnp.int32)
    q = _project_q(p, x, cfg, ctx, positions)
    k_new, v_new = _project_kv(p, x, cfg, ctx, positions)

    s_max = k_cache.shape[1]
    slot = pos % s_max if ring else jnp.minimum(pos, s_max - 1)
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k_new.astype(k_cache.dtype), slot, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v_new.astype(v_cache.dtype), slot, axis=1)
    kv_len = jnp.minimum(pos + 1, s_max)

    if sp and ctx.mesh is not None and "data" in ctx.mesh.axis_names:
        o = sp_decode_attention(q, k_cache, v_cache, kv_len, ctx)
    else:
        # ring buffers hold an arbitrary rotation of the window; positions
        # within the window are order-invariant for softmax attention.
        o = ref_attention(
            q, k_cache.astype(q.dtype), v_cache.astype(q.dtype),
            causal=False, kv_len=kv_len,
        )
    return _out_proj(p, o, ctx), k_cache, v_cache


def sp_decode_attention(
    q: jax.Array,        # (B, 1, Hq, hd) replicated over "data"
    k_cache: jax.Array,  # (B, S, Hkv, hd) sharded over "data" on S
    v_cache: jax.Array,
    kv_len: jax.Array,
    ctx: ShardingCtx,
) -> jax.Array:
    """Distributed flash-decode: each data shard attends over its KV slice,
    then partial outputs are combined with log-sum-exp weights via psum.

    This is the long-context (batch=1) serving path: the 500k-token KV
    cache is sharded over the 16-way "data" axis, so per-chip cache bytes
    drop 16× and the attention reduction parallelizes."""
    mesh = ctx.mesh
    dspec = ctx.rules.resolve(("batch", "kv_seq", "kv_heads", "head_dim"), mesh)
    qspec = ctx.rules.resolve(("batch", None, "act_heads", "head_dim"), mesh)
    hq_global = q.shape[2]
    hkv_global = k_cache.shape[2]
    group = hq_global // hkv_global

    def local(q, k, v, kv_len):
        # q: heads sharded over "model"; k/v: seq sharded over "data",
        # kv heads replicated.  Local q heads are a contiguous global
        # slice, so their GQA kv-head mapping uses GLOBAL head indices.
        b, s_loc, _, hd = k.shape
        hq_loc = q.shape[2]
        head_off = jax.lax.axis_index("model") * hq_loc
        kvh = (head_off + jnp.arange(hq_loc)) // group  # (hq_loc,)
        k_sel = jnp.take(k, kvh, axis=2)  # (b, s_loc, hq_loc, hd)
        v_sel = jnp.take(v, kvh, axis=2)
        seq_off = jax.lax.axis_index("data") * s_loc

        qf = q.astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf * hd**-0.5, k_sel.astype(jnp.float32))
        valid = (jnp.arange(s_loc) + seq_off < kv_len)[None, None, None, :]
        s = jnp.where(valid, s, -jnp.inf)
        m_loc = jnp.max(s, axis=-1)  # (b, hq_loc, 1)
        m_glob = jax.lax.pmax(jnp.where(jnp.isfinite(m_loc), m_loc, -1e30), "data")
        p = jnp.exp(s - m_glob[..., None])
        p = jnp.where(valid, p, 0.0)
        num = jnp.einsum("bhqk,bkhd->bqhd", p, v_sel.astype(jnp.float32))
        den = jnp.sum(p, axis=-1)  # (b, hq_loc, 1)
        num = jax.lax.psum(num, "data")
        den = jax.lax.psum(den, "data")
        o = num / jnp.maximum(den, 1e-30).transpose(0, 2, 1)[..., None]
        return o.astype(q.dtype)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(qspec, dspec, dspec, P()),
        out_specs=qspec,
        check_vma=False,
    )(q, k_cache, v_cache, kv_len)
