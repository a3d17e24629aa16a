"""Fused dynamic-policy (SR / SERPT / conditional-RANK) sojourn evaluator.

Kernel design note — in-tile lockstep simulation of index policies
==================================================================

The paper's stage-level policies (§III-A, §IV-V) re-rank jobs at every
checkpoint: each of the W servers always serves the alive job with the
minimum *conditional* index, where SOAP-style (Scully & Harchol-Balter)
the whole policy is described by its rank function — here a precomputed
``(N, M)`` table ``idx[i, s]`` = job i's priority after surviving ``s``
checkpoints (:func:`repro.core.policies.index_table`).  Exact evaluation
(Eqs. 7-9) therefore needs, per outcome combination, a *simulation*
rather than a prefix sum; the seed path (``evaluator._dynamic_batch``)
runs that simulation over a fully materialized ``(K, N)`` outcome table
and is capped at ``MAX_MATERIALIZED_COMBOS = 2**21``.

These kernels lift the dynamic path to the same streaming scheme as the
static ``sojourn_enum`` op — no ``(K, N)`` table anywhere, exact to
``MAX_EXACT_COMBOS = 2**26``:

* **Tile layout** — the grid is ``(P policies, ceil(K / BLOCK_COMBOS))``
  with the combination axis innermost (sequential).  Each tile owns
  ``BLOCK_COMBOS = 8 x 128`` combination indices as one
  ``(SUBLANES, LANES)`` VPU tile and decodes the stop stage of every job
  on the fly with the shared mixed-radix rule
  ``stage_i(k) = (k // stride_i) % M_i`` (identical decoder and digit
  order as the static kernel and ``enumerate_outcomes``).  The Eq.-8
  weight ``w = prod_i p_{i, stage_i}`` is accumulated during the decode
  via one-hot selects over the small stage axis; tail combinations
  ``k >= K`` carry zero weight.

* **In-tile multi-server lockstep** — every lane then simulates its own
  combination in lockstep over ``sum_i M_i`` completion events on
  ``n_servers = W`` homogeneous servers.  The per-lane state is one
  current-stage register and one ``busy_until`` register per job
  (``+inf`` while not running) plus a busy count, clock and sojourn
  accumulators.  After seating the W smallest-index jobs at t=0 (W
  unrolled dispatch passes), each step (a ``fori_loop``) unrolls two
  passes over the (static) job axis:

  1. *complete*: pop the running job with the earliest ``busy_until``
     via a running minimum with a strict ``<`` compare — ties break
     toward the lowest job position, exactly matching the unified DES's
     event heap (``(time, seq)`` ordering).  The lane clock advances to
     the finish time; if the finished segment reaches the decoded stop
     stage the job's completion time is folded into the successful /
     all-job sojourn accumulators (success == stopping at stage
     ``M_j - 1``), else the job rejoins the queue at its next
     conditional index.  If nothing is running the sentinel "job" ``n``
     matches nothing and the step is a no-op.
  2. *dispatch*: seat the queued job with the minimum conditional index
     ``idx[j, stage_j]`` (one-hot gathers, strict ``<`` running
     minimum, ties by position — ``jnp.argmin`` semantics) on the freed
     server, ``busy_until = clock + stage_durs[j, stage_j]``.  One pass
     suffices: a completion frees exactly one server and requeues at
     most one job, so the queue and the free pool can never both be
     nonempty after it.  With ``W = 1`` the math reduces bitwise to the
     single-server kernel of PR 7 (``busy = clock + dur`` then
     ``clock = busy``).

* **Reduction** — after the step loop the lane holds Eq. (7)'s mean
  sojourn of successful jobs for its combination; the tile adds
  ``w * mean`` into a Kahan-compensated VMEM scratch accumulator that
  persists across the sequential combination tiles and is flushed on the
  last one — the static kernels' ``accumulate``.

The XLA fallback (`_dynamic_enum_xla`) is the identical algorithm as a
``lax.scan`` over combination tiles with the job axis vectorized
(``(T, N)`` state, ``argmin`` selection); it is the default on CPU and
the path the exact evaluator rides on the CPU.  Precision is chosen as in
``ops.py``: the compiled Pallas kernels run in float32 (within
``ops.CHIP_RTOL`` of the float64 oracle); the XLA and interpret paths
follow the ambient x64 mode, float64 inside :func:`repro.runtime.x64`
(the <=1e-9 parity bar).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sojourn_eval import kernel as K
from repro.kernels.sojourn_eval import rng
from repro.kernels.sojourn_eval.ops import (
    compute_dtype,
    precision_scope,
    resolve_impl,
    run_batches,
)
from repro.kernels.sojourn_eval.ref import mixed_radix_strides
from repro.obs import profiling

__all__ = ["sojourn_eval_dynamic", "dynamic_sojourn_enum", "dynamic_sojourn_mc"]

#: Combination indices per XLA scan tile (bounded-memory streaming).
XLA_TILE = 1 << 15


# ---------------------------------------------------------------------------
# Pallas kernel: per-tile lockstep simulation
# ---------------------------------------------------------------------------


def _lockstep_sim(
    sdec, succ, idx_s, dur_s, *, n, m, total_stages, dtype, n_servers=1
):
    """Shared in-tile lockstep multi-server simulation.

    Every lane simulates its own outcome combination (``sdec[j]`` = the
    decoded stop stage of job ``j`` per lane, however it was produced —
    mixed-radix enumeration or the Threefry MC stream) in lockstep over
    ``total_stages`` completion events on ``n_servers`` homogeneous
    servers.  Per-lane state is one current-stage register and one
    ``busy_until`` register per job (``+inf`` while not running).  Each
    step pops the earliest-finishing running job (ties by job position),
    advances the lane clock to its finish time, then seats the
    minimum-index queued job on the freed server; since a completion
    event adds at most one job back to the queue and servers free one at
    a time, a single dispatch pass per step is exhaustive.  The t=0
    seating of the ``min(W, N)`` smallest-index jobs happens before the
    loop.  Returns per-lane ``(tot, tsum, cnt)``: summed successful
    completion times, summed all-job completion times, and the success
    count.  ``n_servers=1`` reproduces the single-server math bitwise
    (``busy = clock + dur`` then ``clock = busy``).
    """
    shape = (K.SUBLANES, K.LANES)
    inf = jnp.full(shape, jnp.inf, dtype)
    zf = jnp.zeros(shape, dtype)
    zi = jnp.zeros(shape, jnp.int32)
    w_srv = min(n_servers, n)

    def _gather(table_j, st, fill):
        v = fill
        for s_ in range(m):
            v = jnp.where(st == s_, table_j[s_], v)
        return v

    def _dispatch_one(stages, busy, nbusy, clock):
        # seat the queued job with the minimum conditional index on a
        # free server; strict < keeps the first minimum (ties by job
        # position).  Sentinel ``n`` when the queue is empty.
        best = inf
        bestj = jnp.full(shape, n, jnp.int32)
        for j in range(n):
            st = stages[j]
            queued = (busy[j] == jnp.inf) & (st <= sdec[j])
            idx_j = jnp.where(queued, _gather(idx_s[j], st, inf), inf)
            better = idx_j < best
            best = jnp.where(better, idx_j, best)
            bestj = jnp.where(better, j, bestj)
        can = (nbusy < w_srv) & (bestj < n)
        new_busy = []
        for j in range(n):
            sel = can & (bestj == j)
            d_j = _gather(dur_s[j], stages[j], zf)
            new_busy.append(jnp.where(sel, clock + d_j, busy[j]))
        return tuple(new_busy), nbusy + can.astype(jnp.int32)

    def step(_, carry):
        stages, busy, nbusy, clock, tot, tsum, cnt = carry
        # completion: pop the running job with the earliest finish time;
        # strict < keeps the first minimum (ties by job position).
        tmin = inf
        cjob = jnp.full(shape, n, jnp.int32)  # sentinel: nothing running
        for j in range(n):
            better = busy[j] < tmin
            tmin = jnp.where(better, busy[j], tmin)
            cjob = jnp.where(better, j, cjob)
        has = cjob < n
        clock = jnp.where(has, tmin, clock)
        fin_any = jnp.zeros(shape, jnp.bool_)
        fin_succ = jnp.zeros(shape, jnp.bool_)
        new_stages, new_busy = [], []
        for j in range(n):
            sel = cjob == j
            st = stages[j]
            fin_j = sel & (st == sdec[j])
            fin_any = fin_any | fin_j
            fin_succ = fin_succ | (fin_j & succ[j])
            new_stages.append(st + sel.astype(jnp.int32))
            new_busy.append(jnp.where(sel, inf, busy[j]))
        nbusy = nbusy - has.astype(jnp.int32)
        tot = jnp.where(fin_succ, tot + clock, tot)
        cnt = cnt + fin_succ.astype(jnp.int32)
        tsum = jnp.where(fin_any, tsum + clock, tsum)
        # refill the freed server: at most one job (re)joined the queue,
        # so one dispatch pass per completion is exhaustive.
        busy2, nbusy = _dispatch_one(
            tuple(new_stages), tuple(new_busy), nbusy, clock
        )
        return tuple(new_stages), busy2, nbusy, clock, tot, tsum, cnt

    stages0 = tuple(zi for _ in range(n))
    busy0 = tuple(inf for _ in range(n))
    nbusy0 = zi
    for _ in range(w_srv):  # t=0: seat the W smallest-index jobs
        busy0, nbusy0 = _dispatch_one(stages0, busy0, nbusy0, zf)
    init = (stages0, busy0, nbusy0, zf, zf, zf, zi)
    _, _, _, _, tot, tsum, cnt = jax.lax.fori_loop(0, total_stages, step, init)
    return tot, tsum, cnt


def _dynamic_kernel(
    strides_ref,  # (1, N) int32 SMEM mixed-radix strides (original job order)
    radix_ref,  # (1, N) int32 SMEM stage counts M_i
    probs_ref,  # (1, N, M) VMEM stop probabilities (0 pad)
    durs_ref,  # (1, N, M) VMEM per-stage service increments (0 pad)
    idx_ref,  # (1, N, M) VMEM this policy's index table (+inf pad)
    succ_ref,  # (1, 1, 1) SMEM out: E[sojourn | successful jobs]
    all_ref,  # (1, 1, 1) SMEM out: E[sojourn | all jobs]
    *scratch,  # K.reduction_scratch
    n: int,
    m: int,
    total_stages: int,
    k_total: int,
    nkt: int,
    n_servers: int,
):
    kt = pl.program_id(1)
    dtype = durs_ref.dtype
    shape = (K.SUBLANES, K.LANES)
    k = K._tile_combo_ids(kt)
    # Scalar tables, hoisted out of the step loop.
    idx_s = [[idx_ref[0, j, s] for s in range(m)] for j in range(n)]
    dur_s = [[durs_ref[0, j, s] for s in range(m)] for j in range(n)]

    # --- decode: stop stage, success flag and Eq.-8 weight per lane -------
    w = (k < k_total).astype(dtype)  # tail tiles carry zero weight
    sdec, succ = [], []
    for j in range(n):
        radix = radix_ref[0, j]
        s = (k // strides_ref[0, j]) % radix
        p = jnp.zeros(shape, dtype)
        for s_ in range(m):  # one-hot gather over the (small) stage axis
            p = jnp.where(s == s_, probs_ref[0, j, s_], p)
        w = w * p
        sdec.append(s)
        succ.append(s == radix - 1)

    # --- lockstep multi-server simulation (stage-boundary preemption) ---
    tot, tsum, cnt = _lockstep_sim(
        sdec, succ, idx_s, dur_s, n=n, m=m, total_stages=total_stages,
        dtype=dtype, n_servers=n_servers,
    )

    # Eq. (7) mean over the successful jobs; Eq. (9) weighted reduction.
    mean = jnp.where(cnt > 0, tot / jnp.maximum(cnt, 1).astype(dtype), 0.0)
    K.accumulate(kt, nkt, (succ_ref, all_ref), scratch, (w * mean, w * (tsum / n)))


def _dynamic_mc_kernel(
    seed_ref,  # (1, 2) int32 SMEM: the two 31-bit Threefry key words
    radix_ref,  # (1, N) int32 SMEM stage counts M_i
    cdf_ref,  # (1, N, M) VMEM stop-probability CDF (cumsum of probs)
    durs_ref,  # (1, N, M) VMEM per-stage service increments (0 pad)
    idx_ref,  # (1, N, M) VMEM this policy's index table (+inf pad)
    succ_ref,  # (1, 1, 1) SMEM out
    all_ref,  # (1, 1, 1) SMEM out
    *scratch,  # K.reduction_scratch
    n: int,
    m: int,
    total_stages: int,
    n_samples: int,
    nkt: int,
    n_servers: int,
):
    """Streamed-MC variant: lanes own sample indices and decode each
    job's stop stage from the Threefry counter stream instead of the
    mixed-radix rule; the lockstep simulation is shared."""
    kt = pl.program_id(1)
    dtype = durs_ref.dtype
    shape = (K.SUBLANES, K.LANES)
    k = K._tile_combo_ids(kt)  # lanes own global sample indices
    key = (seed_ref[0, 0].astype(jnp.uint32), seed_ref[0, 1].astype(jnp.uint32))
    x0 = k.astype(jnp.uint32)
    idx_s = [[idx_ref[0, j, s] for s in range(m)] for j in range(n)]
    dur_s = [[durs_ref[0, j, s] for s in range(m)] for j in range(n)]

    # Uniform MC weights; tail lanes (k >= S) are masked to zero.
    w = (k < n_samples).astype(dtype) * (1.0 / n_samples)
    sdec, succ = [], []
    for j in range(n):
        radix = radix_ref[0, j]
        x1 = (jnp.zeros(shape, jnp.int32) + j).astype(jnp.uint32)
        bits, _ = rng.threefry2x32(jnp, key, x0, x1)
        u = rng.uniform_from_bits(bits, dtype)
        scnt = jnp.zeros(shape, jnp.int32)
        for s_ in range(m):  # inverse-CDF count, same compares as host
            scnt = scnt + (u >= cdf_ref[0, j, s_]).astype(jnp.int32)
        s = jnp.minimum(scnt, radix - 1)
        sdec.append(s)
        succ.append(s == radix - 1)

    tot, tsum, cnt = _lockstep_sim(
        sdec, succ, idx_s, dur_s, n=n, m=m, total_stages=total_stages,
        dtype=dtype, n_servers=n_servers,
    )

    mean = jnp.where(cnt > 0, tot / jnp.maximum(cnt, 1).astype(dtype), 0.0)
    K.accumulate(kt, nkt, (succ_ref, all_ref), scratch, (w * mean, w * (tsum / n)))


@functools.partial(
    jax.jit, static_argnames=("k_total", "total_stages", "n_servers", "interpret")
)
def dynamic_sojourn_enum(
    probs: jax.Array,  # (N, M) padded stop probabilities
    stage_durs: jax.Array,  # (N, M) padded per-stage increments
    idx_tables: jax.Array,  # (P, N, M) per-policy index tables (+inf pad)
    strides: jax.Array,  # (N,) int32 mixed-radix strides
    radix: jax.Array,  # (N,) int32 stage counts
    k_total: int,
    total_stages: int,
    *,
    n_servers: int = 1,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Exact (E[sojourn successful], E[sojourn all]) per policy, fused."""
    p_pols, n, m = idx_tables.shape
    nkt = max(1, pl.cdiv(k_total, K.BLOCK_COMBOS))
    dtype = idx_tables.dtype
    kernel = functools.partial(
        _dynamic_kernel,
        n=n,
        m=m,
        total_stages=total_stages,
        k_total=k_total,
        nkt=nkt,
        n_servers=n_servers,
    )
    out_specs, out_shape = K.scalar_outputs(p_pols, dtype)
    out_succ, out_all = pl.pallas_call(
        kernel,
        grid=(p_pols, nkt),
        in_specs=[
            pl.BlockSpec((1, n), lambda p, kt: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, n), lambda p, kt: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, n, m), lambda p, kt: (0, 0, 0)),
            pl.BlockSpec((1, n, m), lambda p, kt: (0, 0, 0)),
            pl.BlockSpec((1, n, m), lambda p, kt: (p, 0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=K.reduction_scratch(dtype),
        interpret=interpret,
    )(
        strides.reshape(1, n),
        radix.reshape(1, n),
        probs.reshape(1, n, m),
        stage_durs.reshape(1, n, m),
        idx_tables,
    )
    return out_succ[:, 0, 0], out_all[:, 0, 0]


@functools.partial(
    jax.jit, static_argnames=("n_samples", "total_stages", "n_servers", "interpret")
)
def dynamic_sojourn_mc(
    cdf: jax.Array,  # (N, M) stop-probability CDF
    stage_durs: jax.Array,  # (N, M) padded per-stage increments
    idx_tables: jax.Array,  # (P, N, M) per-policy index tables (+inf pad)
    radix: jax.Array,  # (N,) int32 stage counts
    key: jax.Array,  # (2,) int32 Threefry key words, rng.split_seed(seed)
    n_samples: int,
    total_stages: int,
    *,
    n_servers: int = 1,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Streamed-MC (E[sojourn successful], E[sojourn all]) per policy."""
    p_pols, n, m = idx_tables.shape
    nkt = max(1, pl.cdiv(n_samples, K.BLOCK_COMBOS))
    dtype = idx_tables.dtype
    kernel = functools.partial(
        _dynamic_mc_kernel,
        n=n,
        m=m,
        total_stages=total_stages,
        n_samples=n_samples,
        nkt=nkt,
        n_servers=n_servers,
    )
    out_specs, out_shape = K.scalar_outputs(p_pols, dtype)
    out_succ, out_all = pl.pallas_call(
        kernel,
        grid=(p_pols, nkt),
        in_specs=[
            pl.BlockSpec((1, 2), lambda p, kt: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, n), lambda p, kt: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, n, m), lambda p, kt: (0, 0, 0)),
            pl.BlockSpec((1, n, m), lambda p, kt: (0, 0, 0)),
            pl.BlockSpec((1, n, m), lambda p, kt: (p, 0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=K.reduction_scratch(dtype),
        interpret=interpret,
    )(
        key.reshape(1, 2),
        radix.reshape(1, n),
        cdf.reshape(1, n, m),
        stage_durs.reshape(1, n, m),
        idx_tables,
    )
    return out_succ[:, 0, 0], out_all[:, 0, 0]


# ---------------------------------------------------------------------------
# XLA streaming fallback: same algorithm, job axis vectorized
# ---------------------------------------------------------------------------


def _sim_tile_xla(
    s, succ, idx_table, stage_durs, job_ids, *, m, total_stages, n_servers=1
):
    """Shared per-tile lockstep simulation, job axis vectorized.

    ``s`` is the (T, N) decoded stop-stage matrix for this tile (from
    the mixed-radix rule or the Threefry MC stream); returns per-lane
    ``(tot, tsum, cnt)`` as in :func:`_lockstep_sim`.  Same multi-server
    state machine (per-job ``busy_until`` row, completion pop + one
    dispatch pass per step) with ``argmin`` standing in for the unrolled
    running-minimum passes — both keep the first minimum on ties.
    """
    tile, n = s.shape
    dtype = stage_durs.dtype
    inf_row = jnp.full((tile, n), jnp.inf, dtype)
    w_srv = min(n_servers, n)

    def _tables(stage):
        idx = inf_row
        dur = jnp.zeros((tile, n), dtype)
        for s_ in range(m):  # one-hot gather over the stage axis
            hit = stage == s_
            idx = jnp.where(hit, idx_table[None, :, s_], idx)
            dur = jnp.where(hit, stage_durs[None, :, s_], dur)
        return idx, dur

    def _dispatch_one(stage, busy, nbusy, clock):
        idx, dur = _tables(stage)
        queued = (busy == jnp.inf) & (stage <= s)
        idxq = jnp.where(queued, idx, jnp.inf)
        j = jnp.argmin(idxq, axis=1)  # first minimum: ties by position
        can = (nbusy < w_srv) & jnp.isfinite(jnp.min(idxq, axis=1))
        sel = (j[:, None] == job_ids) & can[:, None] & queued
        busy = jnp.where(sel, clock[:, None] + dur, busy)
        return busy, nbusy + can.astype(jnp.int32)

    def body(_, st):
        stage, busy, nbusy, clock, tot, tsum, cnt = st
        tmin = jnp.min(busy, axis=1)
        cj = jnp.argmin(busy, axis=1)  # earliest finish; ties by position
        has = jnp.isfinite(tmin)  # all-idle lanes: no-op
        clock = jnp.where(has, tmin, clock)
        sel = (cj[:, None] == job_ids) & has[:, None]
        fin = sel & (stage == s)
        fin_any = jnp.any(fin, axis=1)
        fin_succ = jnp.any(fin & succ, axis=1)
        tot = tot + jnp.where(fin_succ, clock, 0.0)
        cnt = cnt + fin_succ.astype(jnp.int32)
        tsum = tsum + jnp.where(fin_any, clock, 0.0)
        stage = stage + sel.astype(jnp.int32)
        busy = jnp.where(sel, jnp.inf, busy)
        nbusy = nbusy - has.astype(jnp.int32)
        busy, nbusy = _dispatch_one(stage, busy, nbusy, clock)
        return stage, busy, nbusy, clock, tot, tsum, cnt

    zf = jnp.zeros((tile,), dtype)
    zi = jnp.zeros((tile,), jnp.int32)
    stage0 = jnp.zeros((tile, n), jnp.int32)
    busy0, nbusy0 = inf_row, zi
    for _ in range(w_srv):  # t=0: seat the W smallest-index jobs
        busy0, nbusy0 = _dispatch_one(stage0, busy0, nbusy0, zf)
    init = (stage0, busy0, nbusy0, zf, zf, zf, zi)
    _, _, _, _, tot, tsum, cnt = jax.lax.fori_loop(0, total_stages, body, init)
    return tot, tsum, cnt


def _simulate_tiles(
    idx_table, stage_durs, radix, total, tile, decode, *, total_stages, n_servers
):
    """Eq. (9) for one policy, over ``total`` combinations or samples.

    A ``lax.scan`` over tiles of ``tile`` indices ``k``: ``decode(k)``
    gives their ``(T, N)`` stop stages and ``(T,)`` weights, indices past
    ``total`` weigh zero, and every lane is simulated by
    :func:`_sim_tile_xla`.  ``radix`` is the static tuple of stage counts.
    """
    n, m = stage_durs.shape
    dtype = stage_durs.dtype
    radix_a = jnp.asarray(radix, jnp.int32)[None, :]
    job_ids = jnp.arange(n, dtype=jnp.int32)[None, :]

    def tile_fn(carry, t):
        e_succ, e_all = carry
        k = t * tile + jnp.arange(tile, dtype=jnp.int32)
        s, w = decode(k)
        w = w * (k < total)
        tot, tsum, cnt = _sim_tile_xla(
            s, s == radix_a - 1, idx_table, stage_durs, job_ids, m=m,
            total_stages=total_stages, n_servers=n_servers,
        )
        mean = jnp.where(cnt > 0, tot / jnp.maximum(cnt, 1).astype(dtype), 0.0)
        return (e_succ + jnp.dot(w, mean), e_all + jnp.dot(w, tsum / n)), None

    zero = jnp.zeros((), dtype)
    n_tiles = max(1, -(-total // tile))
    (e_succ, e_all), _ = jax.lax.scan(
        tile_fn, (zero, zero), jnp.arange(n_tiles, dtype=jnp.int32)
    )
    return e_succ, e_all


@functools.partial(
    jax.jit,
    static_argnames=(
        "strides", "radix", "k_total", "tile", "total_stages", "n_servers"
    ),
)
def _dynamic_enum_xla(
    probs, stage_durs, idx_table, *, strides, radix, k_total, tile,
    total_stages, n_servers=1,
):
    """Exact fused dynamic evaluation for one policy; ``strides``/``radix``
    are static tuples so the decode lowers to constant div/mod chains."""
    strides_a = jnp.asarray(strides, jnp.int32)[None, :]
    radix_a = jnp.asarray(radix, jnp.int32)[None, :]
    job_ids = jnp.arange(probs.shape[0], dtype=jnp.int32)[None, :]

    def decode(k):
        s = (k[:, None] // strides_a) % radix_a  # (T, N) on-the-fly decode
        return s, jnp.prod(probs[job_ids, s], axis=1)  # Eq. (8)

    return _simulate_tiles(
        idx_table, stage_durs, radix, k_total, tile, decode,
        total_stages=total_stages, n_servers=n_servers,
    )


@functools.partial(
    jax.jit,
    static_argnames=("radix", "n_samples", "tile", "total_stages", "n_servers"),
)
def _dynamic_mc_xla(
    cdf, stage_durs, idx_table, key2, *, radix, n_samples, tile, total_stages,
    n_servers=1,
):
    """Streamed-MC dynamic evaluation for one policy: per-tile Threefry
    outcome generation (identical counters and compares to the static op
    and the host replay), then the shared lockstep simulation."""
    n = cdf.shape[0]
    radix_a = jnp.asarray(radix, jnp.int32)[None, :]
    x1 = jnp.broadcast_to(jnp.arange(n, dtype=jnp.uint32)[None, :], (tile, n))

    def decode(k):
        x0 = jnp.broadcast_to(k[:, None], (tile, n)).astype(jnp.uint32)
        bits, _ = rng.threefry2x32(jnp, (key2[0], key2[1]), x0, x1)
        u = rng.uniform_from_bits(bits, cdf.dtype)
        s = jnp.sum(u[:, :, None] >= cdf[None, :, :], axis=2).astype(jnp.int32)
        return jnp.minimum(s, radix_a - 1), jnp.full((tile,), 1.0 / n_samples, cdf.dtype)

    return _simulate_tiles(
        idx_table, stage_durs, radix, n_samples, tile, decode,
        total_stages=total_stages, n_servers=n_servers,
    )


# ---------------------------------------------------------------------------
# Public op
# ---------------------------------------------------------------------------


def sojourn_eval_dynamic(
    probs: np.ndarray,  # (N, M) padded stop probabilities
    stage_durs: np.ndarray,  # (N, M) padded per-stage increments
    num_stages: np.ndarray,  # (N,) stage counts
    idx_tables: np.ndarray,  # (P, N, M) or (N, M) policy index tables
    *,
    samples: tuple[int, int] | None = None,  # (seed, n_samples) streamed MC
    n_servers: int = 1,
    impl: str = "auto",
) -> tuple[np.ndarray, np.ndarray]:
    """(E[sojourn successful], E[sojourn all]) per policy; see module doc.

    With ``samples=None``, evaluates all ``K = prod(M_i)`` outcome
    combinations exactly without materializing them, simulating the
    stage-level index policy encoded by each ``(N, M)`` table in
    ``idx_tables``.  With ``samples=(seed, n_samples)``, estimates the
    same quantities by streaming Monte Carlo: outcomes are generated
    in-tile from the counter-based Threefry stream (no ``(S, N)`` table
    anywhere), bitwise identical to the static op's stream and the
    ``ref.ref_mc_outcomes`` host replay for the same seed.
    ``n_servers=W`` evaluates the paper's online multi-server setting
    (W homogeneous servers, stage-boundary preemption, same-instant
    contention by index) — the exact analogue of the unified DES with
    all arrivals at t=0.  Returns ``(P,)`` arrays (pass a single
    ``(N, M)`` table for ``P = 1``).

    When :mod:`repro.obs.profiling` is enabled, each call is timed as a
    ``prof.sojourn_eval.dynamic.<mode>.<impl>`` span, tiled by the phase
    spans ``prof.op_phase.dynamic.<impl>.{prep,put,call,sync}`` of
    :func:`repro.kernels.sojourn_eval.ops.run_batches` (one batch of all
    tables on the kernels, one table a batch on the XLA path).  Each
    kernel call (Pallas or interpret) also counts, from the shapes on the
    host in its ``prep`` phase, ``prof.sojourn_dynamic.calls``,
    ``.policies`` (index tables scored), ``.grid_steps`` (policies times
    combination or sample tiles: the tile simulations the kernel runs) and
    ``.servers`` (W, summed over calls).
    """
    impl = resolve_impl(impl)
    mode = "mc" if samples is not None else "enum"
    with (
        profiling.span(f"sojourn_eval.dynamic.{mode}.{impl}"),
        profiling.phases(f"op_phase.dynamic.{impl}", "prep") as phase,
        precision_scope(impl),
    ):
        return _sojourn_eval_dynamic(
            probs, stage_durs, num_stages, idx_tables,
            samples=samples, n_servers=n_servers, impl=impl, phase=phase,
        )


def _count_kernel_call(policies: int, items: int, n_servers: int) -> None:
    """The ``prof.sojourn_dynamic.*`` counters of one kernel call that
    scores ``policies`` index tables over ``items`` combinations or
    samples on ``n_servers`` servers."""
    profiling.count("sojourn_dynamic.calls")
    profiling.count("sojourn_dynamic.policies", policies)
    tiles = max(1, pl.cdiv(items, K.BLOCK_COMBOS))
    profiling.count("sojourn_dynamic.grid_steps", policies * tiles)
    profiling.count("sojourn_dynamic.servers", n_servers)


def _sojourn_eval_dynamic(
    probs, stage_durs, num_stages, idx_tables, *,
    samples=None, n_servers=1, impl="xla", phase,
) -> tuple[np.ndarray, np.ndarray]:
    if n_servers < 1:
        raise ValueError(f"n_servers must be >= 1; got {n_servers}")
    probs = np.asarray(probs)
    stage_durs = np.asarray(stage_durs)
    num_stages = np.asarray(num_stages, dtype=np.int64)
    idx_tables = np.asarray(idx_tables)
    if idx_tables.ndim == 2:
        idx_tables = idx_tables[None]
    n, m = probs.shape
    if idx_tables.shape[1:] != (n, m):
        raise ValueError(
            f"idx_tables must be (P, {n}, {m}); got {idx_tables.shape}"
        )
    total_stages = int(num_stages.sum())
    radix = num_stages.astype(np.int32)
    fdt = compute_dtype(impl)
    interpret = impl == "interpret"
    xla = impl == "xla"
    if samples is not None:
        seed, n_samples = int(samples[0]), int(samples[1])
        if n_samples <= 0:
            raise ValueError(f"n_samples must be positive; got {n_samples}")
        shared = [np.cumsum(probs, axis=1), stage_durs]  # padded stages add 0 mass
        if xla:
            tile = min(
                XLA_TILE, max(K.BLOCK_COMBOS, 1 << (n_samples - 1).bit_length())
            )
            shared.append(np.asarray(rng.split_seed(seed), np.uint32))

            def per_batch(tables):
                return [tables[0]]

            def call(cdf, durs, key2, table):
                return _dynamic_mc_xla(
                    cdf, durs, table, key2,
                    radix=tuple(int(r) for r in num_stages),
                    n_samples=n_samples,
                    tile=tile,
                    total_stages=total_stages,
                    n_servers=n_servers,
                )
        else:
            key = np.asarray(rng.split_seed(seed), np.int32)

            def per_batch(tables):
                _count_kernel_call(len(tables), n_samples, n_servers)
                return [tables, radix, key]

            def call(cdf, durs, tables, rx, k):
                return dynamic_sojourn_mc(
                    cdf, durs, tables, rx, k, n_samples, total_stages,
                    n_servers=n_servers, interpret=interpret,
                )
    else:
        strides = mixed_radix_strides(num_stages)
        k_total = int(np.prod(num_stages, dtype=np.int64))
        shared = [probs, stage_durs]
        if xla:
            tile = min(XLA_TILE, max(K.BLOCK_COMBOS, 1 << (k_total - 1).bit_length()))

            def per_batch(tables):
                return [tables[0]]

            def call(pr, durs, table):
                return _dynamic_enum_xla(
                    pr, durs, table,
                    strides=tuple(int(s) for s in strides),
                    radix=tuple(int(r) for r in num_stages),
                    k_total=k_total,
                    tile=tile,
                    total_stages=total_stages,
                    n_servers=n_servers,
                )
        else:

            def per_batch(tables):
                _count_kernel_call(len(tables), k_total, n_servers)
                return [tables, strides.astype(np.int32), radix]

            def call(pr, durs, tables, st, rx):
                return dynamic_sojourn_enum(
                    pr, durs, tables, st, rx, k_total, total_stages,
                    n_servers=n_servers, interpret=interpret,
                )
    batch = 1 if xla else len(idx_tables)
    return run_batches(phase, idx_tables, batch, fdt, shared, per_batch, call)
