"""Fused expected-sojourn evaluation of static orders as Pallas kernels.

The exact evaluation scheme (paper §IV-A1, Eqs. 7-9) scores a static
non-preemptive order by enumerating every per-job outcome combination.
The seed implementation materialized the full ``(K, N)`` outcome matrix
in host NumPy (capping K at 2**21); these kernels never materialize it:

* ``sojourn_enum`` — each grid tile owns ``BLOCK_COMBOS`` *combination
  indices* and decodes them on the fly with the mixed-radix rule
  ``stage_i(k) = (k // stride_i) % M_i`` (job 0 is the most-significant
  digit, matching :func:`repro.core.evaluator.enumerate_outcomes`).
  Realized durations / termination probabilities are gathered from the
  padded ``(N, M)`` tables by a one-hot select over the (small) stage
  axis — TPU-friendly: no vector gather, only ``(SUBLANES, LANES)``
  selects.  The per-order completion-time prefix sum runs in the same
  position loop, and the probability-weighted successful-job sojourn
  accumulates, Kahan-compensated, into VMEM scratch tiles that persist
  across the (sequential, innermost) combination-tile grid dimension.

* ``sojourn_outcomes`` — the same fused gather + prefix sum + weighted
  reduction for an *explicit* outcome matrix (Monte-Carlo samples or a
  shared exact table).  The ``(K, N)`` int32 matrix is streamed through
  VMEM in ``(SUBLANES, LANES)``-shaped tiles laid out stage-major, so
  the float duration/success matrices of the seed path are never built.

* ``sojourn_mc`` — streaming Monte-Carlo: each grid tile owns
  ``BLOCK_COMBOS`` *sample indices* and generates the per-job outcome
  in-register from the counter-based Threefry stream
  (:mod:`repro.kernels.sojourn_eval.rng`): ``(seed, sample, job)`` ->
  uniform -> inverse-CDF count over the cached per-job CDF.  No
  ``(S, N)`` sample table exists on host or device, and the counter is
  keyed by *original* job id, so every order (and the dynamic op's
  policies) evaluated under one seed sees the identical outcome stream
  (common random numbers).

Both kernels take per-*order* inputs (grid dim 0) whose job axis is
pre-permuted by the caller (``ops.py``), so position ``pos`` in the
kernel loop *is* service position: the running sum ``t`` after ``pos``
steps is the completion time of the job served ``pos``-th.

Accumulation happens in the input dtype.  ``ops.sojourn_eval`` passes
float32 when the kernels are compiled for a TPU (Mosaic has no 64-bit
types; ``ops.CHIP_RTOL`` bounds the error against the float64 oracle),
and the ambient x64 mode's float in interpret mode, where the <=1e-9
parity tests run them in float64.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sojourn_eval import rng

__all__ = [
    "sojourn_enum",
    "sojourn_outcomes",
    "sojourn_mc",
    "BLOCK_COMBOS",
    "SUBLANES",
    "LANES",
]

SUBLANES = 8  # float32 min sublane count
LANES = 128  # TPU lane width
#: Combination indices decoded / streamed per grid tile.
BLOCK_COMBOS = SUBLANES * LANES


def _tile_combo_ids(kt: jax.Array) -> jax.Array:
    """(SUBLANES, LANES) combination indices owned by tile ``kt``."""
    row = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 1)
    return kt * BLOCK_COMBOS + row * LANES + col


def reduction_scratch(dtype) -> list:
    """VMEM scratch of the tiled reduction: a running-sum tile and its
    Kahan compensation tile for each of the two results."""
    return [pltpu.VMEM((SUBLANES, LANES), dtype) for _ in range(4)]


def accumulate(kt, nkt, outs, scratch, terms):
    """Add one tile's per-lane terms into the scratch; flush on the last tile.

    Each lane sums one term per combination tile, up to 2**16 of them at
    K = 2**26.  A plain float32 running sum loses about 1e-4 relative
    there; the Kahan compensation keeps it near float32 rounding.  The
    combination axis is the innermost, sequential grid dimension, so
    ``kt == 0`` starts each order's (or policy's) sum.
    """

    @pl.when(kt == 0)
    def _init():
        for ref in scratch:
            ref[...] = jnp.zeros_like(ref)

    for i, x in enumerate(terms):
        acc, comp = scratch[2 * i], scratch[2 * i + 1]
        y = x - comp[...]
        t = acc[...] + y
        comp[...] = (t - acc[...]) - y
        acc[...] = t

    @pl.when(kt == nkt - 1)
    def _flush():
        for i, out in enumerate(outs):
            out[0, 0, 0] = jnp.sum(scratch[2 * i][...] - scratch[2 * i + 1][...])


# TPU block layout.  Mosaic accepts a block only if its last two dims are
# (8, 128)-aligned or span the whole array, and SMEM holds 1 MiB, so a
# whole (P, N) table of per-order scalars does not fit at P = 4096.  Each
# grid row p therefore gets its own (1, 1, N) slice of a (P, 1, N) table,
# and writes its two scalars through a (1, 1, 1) SMEM block of a (P, 1, 1)
# output.


def per_row_smem(n: int) -> pl.BlockSpec:
    """SMEM block holding row ``p`` of a ``(P, 1, n)`` scalar table."""
    return pl.BlockSpec((1, 1, n), lambda p, kt: (p, 0, 0), memory_space=pltpu.SMEM)


def scalar_outputs(rows: int, dtype):
    """``(out_specs, out_shape)`` for the two per-row ``(rows, 1, 1)`` results."""
    spec = pl.BlockSpec((1, 1, 1), lambda p, kt: (p, 0, 0), memory_space=pltpu.SMEM)
    shape = jax.ShapeDtypeStruct((rows, 1, 1), dtype)
    return [spec, spec], [shape, shape]


# ---------------------------------------------------------------------------
# Enumeration mode: decode combination indices on the fly (Eqs. 7-9 exact)
# ---------------------------------------------------------------------------


def _enum_kernel(
    strides_ref,  # (1, 1, N) int32 SMEM, per-order permuted mixed-radix strides
    radix_ref,  # (1, 1, N) int32 SMEM, per-order permuted stage counts M_i
    sizes_ref,  # (1, N, M) VMEM, per-order permuted cumulative sizes
    probs_ref,  # (1, N, M) VMEM, per-order permuted stop probabilities
    succ_ref,  # (1, 1, 1) SMEM out: E[sojourn | successful jobs]
    all_ref,  # (1, 1, 1) SMEM out: E[sojourn | all jobs]
    *scratch,  # reduction_scratch
    n: int,
    m: int,
    k_total: int,
    nkt: int,
):
    kt = pl.program_id(1)
    dtype = sizes_ref.dtype
    k = _tile_combo_ids(kt)
    # Eq. (8): combination probability = prod_i p_{i, stage_i(k)}; the tail
    # tile is masked by zeroing its weight (k >= K contributes nothing).
    w = (k < k_total).astype(dtype)
    t = jnp.zeros((SUBLANES, LANES), dtype)  # completion time at position pos
    tsum = jnp.zeros((SUBLANES, LANES), dtype)  # sum of completion times
    tot = jnp.zeros((SUBLANES, LANES), dtype)  # sum over successful jobs
    cnt = jnp.zeros((SUBLANES, LANES), jnp.int32)  # successes l(k)
    for pos in range(n):
        stride = strides_ref[0, 0, pos]
        radix = radix_ref[0, 0, pos]
        s = (k // stride) % radix  # on-the-fly mixed-radix decode
        d = jnp.zeros((SUBLANES, LANES), dtype)
        p = jnp.zeros((SUBLANES, LANES), dtype)
        for j in range(m):  # one-hot gather over the (small) stage axis
            hit = s == j
            d = jnp.where(hit, sizes_ref[0, pos, j], d)
            p = jnp.where(hit, probs_ref[0, pos, j], p)
        w = w * p
        t = t + d
        succ = s == radix - 1
        tot = jnp.where(succ, tot + t, tot)
        cnt = cnt + succ.astype(jnp.int32)
        tsum = tsum + t
    # Eq. (7): mean sojourn of the l(k) successful jobs (0 when l = 0);
    # Eq. (9): the probability-weighted sum, tiled into the scratch.
    mean = jnp.where(cnt > 0, tot / jnp.maximum(cnt, 1).astype(dtype), 0.0)
    accumulate(kt, nkt, (succ_ref, all_ref), scratch, (w * mean, w * (tsum / n)))


@functools.partial(jax.jit, static_argnames=("k_total", "interpret"))
def sojourn_enum(
    sizes_p: jax.Array,  # (P, N, M) per-order permuted cumulative sizes
    probs_p: jax.Array,  # (P, N, M) per-order permuted probabilities
    strides_p: jax.Array,  # (P, N) int32 permuted mixed-radix strides
    radix_p: jax.Array,  # (P, N) int32 permuted stage counts
    k_total: int,
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Exact (E[sojourn successful], E[sojourn all]) per order, fused."""
    p_orders, n, m = sizes_p.shape
    nkt = max(1, pl.cdiv(k_total, BLOCK_COMBOS))
    dtype = sizes_p.dtype
    kernel = functools.partial(_enum_kernel, n=n, m=m, k_total=k_total, nkt=nkt)
    out_specs, out_shape = scalar_outputs(p_orders, dtype)
    out_succ, out_all = pl.pallas_call(
        kernel,
        grid=(p_orders, nkt),
        in_specs=[
            per_row_smem(n),
            per_row_smem(n),
            pl.BlockSpec((1, n, m), lambda p, kt: (p, 0, 0)),
            pl.BlockSpec((1, n, m), lambda p, kt: (p, 0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=reduction_scratch(dtype),
        interpret=interpret,
    )(strides_p[:, None], radix_p[:, None], sizes_p, probs_p)
    return out_succ[:, 0, 0], out_all[:, 0, 0]


# ---------------------------------------------------------------------------
# Explicit-outcome mode: stream a (K, N) outcome matrix (MC / shared tables)
# ---------------------------------------------------------------------------


def _outcomes_kernel(
    order_ref,  # (1, 1, N) int32 SMEM: original job id served at each position
    radix_ref,  # (1, 1, N) int32 SMEM, per-order permuted stage counts
    sizes_ref,  # (1, N, M) VMEM, per-order permuted cumulative sizes
    outcomes_ref,  # (N, 1, SUBLANES, LANES) int32 VMEM, original job indexing
    weights_ref,  # (1, SUBLANES, LANES) VMEM, zero-padded combination weights
    succ_ref,  # (1, 1, 1) SMEM out
    all_ref,  # (1, 1, 1) SMEM out
    *scratch,  # reduction_scratch
    n: int,
    m: int,
    nkt: int,
):
    kt = pl.program_id(1)
    dtype = sizes_ref.dtype
    w = weights_ref[0]  # tail tiles are weight-padded with zeros
    t = jnp.zeros((SUBLANES, LANES), dtype)
    tsum = jnp.zeros((SUBLANES, LANES), dtype)
    tot = jnp.zeros((SUBLANES, LANES), dtype)
    cnt = jnp.zeros((SUBLANES, LANES), jnp.int32)
    for pos in range(n):
        job = order_ref[0, 0, pos]
        radix = radix_ref[0, 0, pos]
        s = outcomes_ref[job, 0]  # (SUBLANES, LANES) realized stop stages
        d = jnp.zeros((SUBLANES, LANES), dtype)
        for j in range(m):
            d = jnp.where(s == j, sizes_ref[0, pos, j], d)
        t = t + d
        succ = s == radix - 1
        tot = jnp.where(succ, tot + t, tot)
        cnt = cnt + succ.astype(jnp.int32)
        tsum = tsum + t
    mean = jnp.where(cnt > 0, tot / jnp.maximum(cnt, 1).astype(dtype), 0.0)
    accumulate(kt, nkt, (succ_ref, all_ref), scratch, (w * mean, w * (tsum / n)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def sojourn_outcomes(
    sizes_p: jax.Array,  # (P, N, M) per-order permuted cumulative sizes
    radix_p: jax.Array,  # (P, N) int32 permuted stage counts
    orders: jax.Array,  # (P, N) int32 original job ids by position
    outcomes_t: jax.Array,  # (N, KT, SUBLANES, LANES) int32 streamed tiles
    weights_t: jax.Array,  # (KT, SUBLANES, LANES) zero-padded weights
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused static-order evaluation over an explicit outcome matrix."""
    p_orders, n, m = sizes_p.shape
    nkt = weights_t.shape[0]
    dtype = sizes_p.dtype
    kernel = functools.partial(_outcomes_kernel, n=n, m=m, nkt=nkt)
    out_specs, out_shape = scalar_outputs(p_orders, dtype)
    out_succ, out_all = pl.pallas_call(
        kernel,
        grid=(p_orders, nkt),
        in_specs=[
            per_row_smem(n),
            per_row_smem(n),
            pl.BlockSpec((1, n, m), lambda p, kt: (p, 0, 0)),
            pl.BlockSpec((n, 1, SUBLANES, LANES), lambda p, kt: (0, kt, 0, 0)),
            pl.BlockSpec((1, SUBLANES, LANES), lambda p, kt: (kt, 0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=reduction_scratch(dtype),
        interpret=interpret,
    )(orders[:, None], radix_p[:, None], sizes_p, outcomes_t, weights_t)
    return out_succ[:, 0, 0], out_all[:, 0, 0]


# ---------------------------------------------------------------------------
# Streaming Monte-Carlo mode: counter-based RNG outcome generation in-tile
# ---------------------------------------------------------------------------


def _mc_kernel(
    seed_ref,  # (1, 2) int32 SMEM: the two 31-bit Threefry key words
    order_ref,  # (1, 1, N) int32 SMEM: original job id served at each position
    radix_ref,  # (1, 1, N) int32 SMEM, per-order permuted stage counts
    sizes_ref,  # (1, N, M) VMEM, per-order permuted cumulative sizes
    cdf_ref,  # (1, N, M) VMEM, per-order permuted stop-probability CDF
    succ_ref,  # (1, 1, 1) SMEM out
    all_ref,  # (1, 1, 1) SMEM out
    *scratch,  # reduction_scratch
    n: int,
    m: int,
    n_samples: int,
    nkt: int,
):
    kt = pl.program_id(1)
    dtype = sizes_ref.dtype
    k = _tile_combo_ids(kt)  # lanes own global sample indices
    key = (seed_ref[0, 0].astype(jnp.uint32), seed_ref[0, 1].astype(jnp.uint32))
    x0 = k.astype(jnp.uint32)
    # Uniform MC weights; tail lanes (k >= S) are masked to zero.
    w = (k < n_samples).astype(dtype) * (1.0 / n_samples)
    t = jnp.zeros((SUBLANES, LANES), dtype)
    tsum = jnp.zeros((SUBLANES, LANES), dtype)
    tot = jnp.zeros((SUBLANES, LANES), dtype)
    cnt = jnp.zeros((SUBLANES, LANES), jnp.int32)
    for pos in range(n):
        job = order_ref[0, 0, pos]  # RNG counter keyed by ORIGINAL job id
        radix = radix_ref[0, 0, pos]
        x1 = (jnp.zeros((SUBLANES, LANES), jnp.int32) + job).astype(jnp.uint32)
        bits, _ = rng.threefry2x32(jnp, key, x0, x1)
        u = rng.uniform_from_bits(bits, dtype)
        # Inverse-CDF count, identical comparisons to the host replay.
        scnt = jnp.zeros((SUBLANES, LANES), jnp.int32)
        for j in range(m):
            scnt = scnt + (u >= cdf_ref[0, pos, j]).astype(jnp.int32)
        s = jnp.minimum(scnt, radix - 1)
        d = jnp.zeros((SUBLANES, LANES), dtype)
        for j in range(m):
            d = jnp.where(s == j, sizes_ref[0, pos, j], d)
        t = t + d
        succ = s == radix - 1
        tot = jnp.where(succ, tot + t, tot)
        cnt = cnt + succ.astype(jnp.int32)
        tsum = tsum + t
    mean = jnp.where(cnt > 0, tot / jnp.maximum(cnt, 1).astype(dtype), 0.0)
    accumulate(kt, nkt, (succ_ref, all_ref), scratch, (w * mean, w * (tsum / n)))


@functools.partial(jax.jit, static_argnames=("n_samples", "interpret"))
def sojourn_mc(
    sizes_p: jax.Array,  # (P, N, M) per-order permuted cumulative sizes
    cdf_p: jax.Array,  # (P, N, M) per-order permuted stop-probability CDF
    radix_p: jax.Array,  # (P, N) int32 permuted stage counts
    orders: jax.Array,  # (P, N) int32 original job ids by position
    key: jax.Array,  # (2,) int32 Threefry key words, rng.split_seed(seed)
    n_samples: int,
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Streamed-MC (E[sojourn successful], E[sojourn all]) per order."""
    p_orders, n, m = sizes_p.shape
    nkt = max(1, pl.cdiv(n_samples, BLOCK_COMBOS))
    dtype = sizes_p.dtype
    kernel = functools.partial(
        _mc_kernel, n=n, m=m, n_samples=n_samples, nkt=nkt
    )
    out_specs, out_shape = scalar_outputs(p_orders, dtype)
    out_succ, out_all = pl.pallas_call(
        kernel,
        grid=(p_orders, nkt),
        in_specs=[
            pl.BlockSpec((1, 2), lambda p, kt: (0, 0), memory_space=pltpu.SMEM),
            per_row_smem(n),
            per_row_smem(n),
            pl.BlockSpec((1, n, m), lambda p, kt: (p, 0, 0)),
            pl.BlockSpec((1, n, m), lambda p, kt: (p, 0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=reduction_scratch(dtype),
        interpret=interpret,
    )(key.reshape(1, 2), orders[:, None], radix_p[:, None], sizes_p, cdf_p)
    return out_succ[:, 0, 0], out_all[:, 0, 0]
