"""Fused expected-sojourn evaluation of static orders as Pallas kernels.

The exact evaluation scheme (paper §IV-A1, Eqs. 7-9) scores a static
non-preemptive order by enumerating every per-job outcome combination.
The seed implementation materialized the full ``(K, N)`` outcome matrix
in host NumPy (capping K at 2**21); these kernels never materialize it:

* ``sojourn_enum`` — grid ``(order block, combination tile)``.  Each
  tile owns ``BLOCK_COMBOS`` *combination indices* and decodes them on
  the fly with the mixed-radix rule ``stage_j(k) = (k // stride_j) %
  M_j`` (job 0 is the most-significant digit, matching
  :func:`repro.core.evaluator.enumerate_outcomes`), with one integer
  division a job, once per job in original job indexing: the realized
  duration and success mask go to
  VMEM scratch, and the combination weight (Eq. 8) and success count
  stay in registers.  Durations / probabilities are gathered from the
  ``(N, M)`` tables by a one-hot select over the (small) stage axis —
  TPU-friendly: no vector gather, only ``(SUBLANES, LANES)`` selects.
  A loop over the block's orders then reads each position's job id
  from SMEM and the scratch by it, so only the per-order completion-time
  prefix sum and the two weighted sums remain per order.  Inputs: the
  job group's tables once, unpermuted (a float ``(2, N, M)`` array and
  an int32 ``(2, N)`` one), and the orders' job ids laid out by
  :func:`enum_blocked_orders`: each grid step sees its own block's ids
  in SMEM and writes its block's answers to SMEM blocks, so SMEM holds
  one block whatever the number of orders, and one call scores them
  all.  When the combinations span tiles, each order's sum is
  Kahan-compensated in VMEM scratch across the (sequential, innermost)
  tile axis.

* ``sojourn_mc`` — streaming Monte-Carlo: each grid tile owns
  ``BLOCK_COMBOS`` *sample indices* and generates the per-job outcome
  in-register from the counter-based Threefry stream
  (:mod:`repro.kernels.sojourn_eval.rng`): ``(seed, sample, job)`` ->
  uniform -> inverse-CDF count over the cached per-job CDF.  No
  ``(S, N)`` sample table exists on host or device, and the counter is
  keyed by *original* job id, so every order (and the dynamic op's
  policies) evaluated under one seed sees the identical outcome stream
  (common random numbers).

``sojourn_mc`` takes per-*order* inputs (grid dim 0) whose job axis is
pre-permuted by the caller (``ops.py``).  In both kernels, step ``pos``
of the position loop *is* service position: the running sum ``t`` after
``pos`` steps is the completion time of the job served ``pos``-th.

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
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sojourn_eval import rng

__all__ = [
    "sojourn_enum",
    "enum_order_block",
    "enum_grid",
    "enum_blocked_orders",
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


def _kahan_add(acc, comp, idx, x):
    """Kahan-add tile ``x`` into ``acc[idx]`` with compensation ``comp[idx]``."""
    y = x - comp[idx]
    t = acc[idx] + y
    comp[idx] = (t - acc[idx]) - y
    acc[idx] = t


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
        _kahan_add(scratch[2 * i], scratch[2 * i + 1], ..., x)

    @pl.when(kt == nkt - 1)
    def _flush():
        for i, out in enumerate(outs):
            out[0, 0, 0] = jnp.sum(scratch[2 * i][...] - scratch[2 * i + 1][...])


# TPU block layout.  Mosaic accepts a block only if its last two dims are
# (8, 128)-aligned or span the whole array, and SMEM holds 1 MiB, so a
# whole (P, N) table of per-order scalars does not fit at P = 4096 (nor a
# flat one at 8! orders).  Each grid row p therefore gets its own
# (1, 1, n) slice of a (P, 1, n) table and writes its answers through
# (1, 1, w) SMEM blocks of (P, 1, w) outputs: a row is one order of
# sojourn_mc (w = 1), or one block of orders of sojourn_enum.


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


#: Most orders a grid step scores against one decoded combination tile.
#: When one tile holds every combination (``K <= BLOCK_COMBOS``: OPTIMAL's
#: N! orders at N <= 10, M = 2) an order's answers are final after its
#: one tile.  When the combinations span tiles, each order of the block
#: keeps its four Kahan tiles in VMEM across them, 16 KiB an order in
#: float32.  A block's job ids and answers are its SMEM: at N = 8, 16 KiB
#: of ids.
ORDER_BLOCK = 512
ORDER_BLOCK_TILED = 32
#: Orders scored in one trip of the order loop when one tile holds K.  With
#: Kahan sums across tiles a trip scores one order: a trip that ran past
#: the last order would add that order's terms twice.
ORDER_UNROLL = 4


def enum_order_block(p_orders: int, k_total: int) -> tuple[int, int]:
    """``(block, unroll)`` of :func:`sojourn_enum` for ``p_orders`` orders
    over ``k_total`` combinations: orders a grid step, and orders a trip of
    its order loop.  The fewest blocks the cap allows, split evenly, each
    whole trips."""
    if k_total <= BLOCK_COMBOS:
        unroll, cap = min(ORDER_UNROLL, p_orders), ORDER_BLOCK
    else:
        unroll, cap = 1, ORDER_BLOCK_TILED
    even = pl.cdiv(p_orders, pl.cdiv(p_orders, cap))
    return pl.cdiv(even, unroll) * unroll, unroll


def enum_grid(p_orders: int, k_total: int) -> tuple[int, int]:
    """Grid of :func:`sojourn_enum`: ``(order blocks, combination tiles)``."""
    block, _ = enum_order_block(p_orders, k_total)
    return pl.cdiv(p_orders, block), max(1, pl.cdiv(k_total, BLOCK_COMBOS))


def enum_blocked_orders(orders: np.ndarray, k_total: int) -> np.ndarray:
    """The ``(P, N)`` orders as :func:`sojourn_enum` reads them: an int32
    ``(order blocks, 1, block * N)`` array, one row of job ids a block,
    order-major.  The tail block is filled with copies of the last order;
    the kernel's answers there are not the orders' and are dropped."""
    p_orders, n = orders.shape
    block, _ = enum_order_block(p_orders, k_total)
    blocks, _ = enum_grid(p_orders, k_total)
    ids = orders.astype(np.int32, copy=False)
    if blocks * block > p_orders:  # np.pad costs tens of microseconds even when it pads nothing
        tail = np.broadcast_to(ids[-1], (blocks * block - p_orders, n))
        ids = np.concatenate([ids, tail])
    return ids.reshape(blocks, 1, block * n)


def _enum_kernel(
    tables_ref,  # (2, N, M) VMEM: cumulative sizes, stop probabilities
    ints_ref,  # (2, N) int32 SMEM: mixed-radix strides, stage counts M_j
    orders_ref,  # (1, 1, B * N) int32 SMEM: this block's job ids, order-major
    succ_ref,  # (1, 1, B) SMEM out: E[sojourn | successful jobs] per order
    all_ref,  # (1, 1, B) SMEM out: E[sojourn | all jobs] per order
    dur_ref,  # (N, SUBLANES, LANES) VMEM scratch: realized durations
    won_ref,  # (N, SUBLANES, LANES) VMEM scratch: 1 where the job succeeds
    *acc,  # nkt > 1: four (B, SUBLANES, LANES) Kahan tiles, as accumulate's
    n: int,
    m: int,
    block: int,
    unroll: int,
    p_orders: int,
    k_total: int,
    nkt: int,
):
    b, kt = pl.program_id(0), pl.program_id(1)
    dtype = tables_ref.dtype
    k = _tile_combo_ids(kt)
    # Decode the tile once per job, in original job indexing.  Eq. (8):
    # combination probability = prod_j p_{j, stage_j(k)}; the tail tile is
    # masked by zeroing its weight (k >= K contributes nothing).
    w = (k < k_total).astype(dtype)
    cnt = jnp.zeros((SUBLANES, LANES), jnp.int32)  # successes l(k)
    for j in range(n):
        # On-the-fly mixed-radix decode, one vector integer division a job
        # (the VPU divides integers in software), independent across jobs:
        # q_j = k // stride_j, and stage_j(k) = q_j % M_j = q_j - q_{j-1} M_j
        # since q_{j-1} = q_j // M_j.  For k < K, q_0 < M_0 is the stage.
        quot = k // ints_ref[0, j]
        s = quot if j == 0 else quot - prev * ints_ref[1, j]
        prev = quot
        d = jnp.zeros((SUBLANES, LANES), dtype)
        p = jnp.zeros((SUBLANES, LANES), dtype)
        for i in range(m):  # one-hot gather over the (small) stage axis
            hit = s == i
            d = jnp.where(hit, tables_ref[0, j, i], d)
            p = jnp.where(hit, tables_ref[1, j, i], p)
        w = w * p
        succ = s == ints_ref[1, j] - 1
        cnt = cnt + succ.astype(jnp.int32)
        dur_ref[j] = d
        won_ref[j] = succ.astype(dtype)
    if nkt > 1:

        @pl.when(kt == 0)
        def _init():
            for ref in acc:
                ref[...] = jnp.zeros_like(ref)

    has_won = cnt > 0
    n_won = jnp.maximum(cnt, 1).astype(dtype)

    def score(o):
        # Only the prefix sums depend on the order: position pos serves
        # job orders[o, pos], and t after pos steps is its completion time.
        t = jnp.zeros((SUBLANES, LANES), dtype)
        tsum = jnp.zeros((SUBLANES, LANES), dtype)  # sum of completion times
        tot = jnp.zeros((SUBLANES, LANES), dtype)  # sum over successful jobs
        for pos in range(n):
            job = orders_ref[0, 0, o * n + pos]
            t = t + dur_ref[job]
            tot = tot + won_ref[job] * t  # adds t or exactly 0
            tsum = tsum + t
        # Eq. (7): mean sojourn of the l(k) successful jobs (0 when l = 0);
        # Eq. (9): the probability-weighted sum over the tile.
        mean = jnp.where(has_won, tot / n_won, 0.0)
        terms = (w * mean, w * (tsum / n))
        outs = (succ_ref, all_ref)
        if nkt == 1:
            for out, x in zip(outs, terms):
                out[0, 0, o] = jnp.sum(x)
            return
        for i, x in enumerate(terms):
            _kahan_add(acc[2 * i], acc[2 * i + 1], o, x)

        @pl.when(kt == nkt - 1)
        def _flush():
            for i, out in enumerate(outs):
                out[0, 0, o] = jnp.sum(acc[2 * i][o] - acc[2 * i + 1][o])

    # ``unroll`` orders a trip give the scheduler independent work.  The
    # tail block scores only the orders that exist; a trip that runs past
    # the last order scores its copies (enum_blocked_orders), whose
    # answers are dropped.
    def trip(i, carry):
        for u in range(unroll):
            score(i * unroll + u)
        return carry

    n_valid = jnp.minimum(block, p_orders - b * block)
    jax.lax.fori_loop(0, (n_valid + unroll - 1) // unroll, trip, 0)


@functools.partial(jax.jit, static_argnames=("k_total", "p_orders", "interpret"))
def sojourn_enum(
    tables: jax.Array,  # (2, N, M) cumulative sizes and stop probabilities
    ints: jax.Array,  # (2, N) int32 mixed-radix strides and stage counts
    orders: jax.Array,  # (blocks, 1, block * N) int32, enum_blocked_orders
    k_total: int,
    p_orders: int,
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Exact (E[sojourn successful], E[sojourn all]) of ``p_orders`` orders,
    fused, as two ``(blocks, 1, block)`` arrays: order ``q``'s answers are
    at flat index ``q``, and those past ``p_orders`` are padding.

    Grid ``(order block, combination tile)``: each step decodes its tile
    once for the job group and scores a block of
    :func:`enum_order_block` orders against that decode.  A step's job
    ids and answers are SMEM blocks indexed by the order block alone, so
    the tiles of one block read its ids once, and SMEM holds one block
    of them whatever ``p_orders`` is.
    """
    _, n, m = tables.shape
    block, unroll = enum_order_block(p_orders, k_total)
    grid = enum_grid(p_orders, k_total)
    if orders.shape != (grid[0], 1, block * n):
        raise ValueError(
            f"orders must be laid out by enum_blocked_orders as {(grid[0], 1, block * n)};"
            f" got {orders.shape}"
        )
    nkt = grid[1]
    dtype = tables.dtype
    kernel = functools.partial(
        _enum_kernel, n=n, m=m, block=block, unroll=unroll, p_orders=p_orders,
        k_total=k_total, nkt=nkt,
    )
    scratch = [pltpu.VMEM((n, SUBLANES, LANES), dtype) for _ in range(2)]
    if nkt > 1:
        scratch += [pltpu.VMEM((block, SUBLANES, LANES), dtype) for _ in range(4)]
    out_shape = jax.ShapeDtypeStruct((grid[0], 1, block), dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            per_row_smem(block * n),
        ],
        out_specs=[per_row_smem(block), per_row_smem(block)],
        out_shape=[out_shape, out_shape],
        scratch_shapes=scratch,
        interpret=interpret,
    )(tables, ints, orders)


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
