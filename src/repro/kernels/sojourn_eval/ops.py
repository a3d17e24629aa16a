"""Public fused sojourn-evaluation op with implementation dispatch.

``impl``:
  * "xla"       — tiled jit implementation: a ``lax.scan`` over
                  combination tiles decodes the mixed-radix indices on
                  the fly and accumulates the weighted reduction.  Same
                  streaming structure as the Pallas kernel (bounded
                  memory, no (K, N) host materialization); default on
                  CPU and the path the exact evaluator rides.
  * "pallas"    — the TPU Pallas kernels (compiled via Mosaic).
  * "interpret" — the Pallas kernels interpreted on CPU (parity tests).
  * "auto"      — "pallas" on TPU backends, else "xla".

Three entry modes, mirroring :mod:`repro.core.evaluator`'s sources of
outcome combinations:

* ``sojourn_eval(..., outcomes=None)`` — *exact enumeration*: evaluates
  all ``K = prod(M_i)`` combinations without ever materializing them
  (supports K up to ``repro.core.evaluator.MAX_EXACT_COMBOS``).
* ``sojourn_eval(..., outcomes=, weights=)`` — *explicit outcomes*:
  Monte-Carlo samples or a shared exact table; the float duration and
  success matrices of the seed path are never built host-side.
* ``sojourn_eval(..., samples=(seed, n_samples))`` — *streaming Monte
  Carlo*: outcomes are generated inside the tiles from the counter-based
  Threefry stream (:mod:`repro.kernels.sojourn_eval.rng`) and an
  inverse-CDF search, so no ``(S, N)`` sample table exists on host or
  device and sample counts are compute-bound rather than
  table-memory-bound.  The stream is keyed by original job id: every
  order/policy evaluated under one seed sees identical outcomes
  (common random numbers), and ``ref.ref_mc_outcomes`` replays the
  stream host-side bitwise for parity.

Precision: ``impl="pallas"`` always runs the kernels in float32, since
Mosaic has no 64-bit types; its error against the float64 oracle is
bounded per entry mode by :data:`CHIP_RTOL`.  ``"xla"`` and
``"interpret"`` follow the ambient JAX x64 mode: the evaluator calls
this op under :func:`repro.runtime.x64`, so on the CPU everything
accumulates in float64 (<=1e-9 parity with the dense oracle).  The
host-side table preparation is float64 in every mode.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.sojourn_eval import kernel as K
from repro.kernels.sojourn_eval import rng
from repro.kernels.sojourn_eval.ref import mixed_radix_strides
from repro.obs import profiling

__all__ = ["sojourn_eval", "CHIP_RTOL"]

Impl = Literal["auto", "xla", "pallas", "interpret"]

#: Largest relative error of the float32 Pallas path against the float64
#: oracle, per entry mode, at the sizes ``chip_smoke.py`` runs.  Shared by
#: that script and the float32 parity tests.  On a TPU v5e the largest
#: errors seen were 4.1e-7 (enum, 9! orders and K = 2**26), 2.3e-8
#: (outcomes) and 3.8e-8 (mc, 2**20 samples).  "mc" has room for a few
#: samples whose float32 uniform lands on the other side of a float32 CDF
#: entry than in float64: each moves the mean by about 1/n_samples.
CHIP_RTOL = {"enum": 1e-6, "outcomes": 1e-6, "mc": 1e-5}

#: Combination indices per XLA scan tile (bounded-memory streaming).
XLA_TILE = 1 << 15
#: Soft cap on bytes of per-tile intermediates in the XLA path.
_TILE_BYTES_BUDGET = 256 << 20


def resolve_impl(impl: str) -> str:
    """``"auto"`` is the Pallas kernels on a TPU and the XLA path elsewhere."""
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in ("xla", "pallas", "interpret"):
        raise ValueError(f"unknown impl {impl!r}; options: auto/xla/pallas/interpret")
    return impl


def compute_dtype(impl: str):
    """float32 for the compiled kernels, else the ambient x64 mode's float."""
    return jnp.float32 if impl == "pallas" else jnp.result_type(float)


def precision_scope(impl: str):
    """Scope an op call runs in: x64 off for the compiled kernels.

    Under x64 the kernels' index maps and literals trace as 64-bit
    integers, which Mosaic cannot lower, so ``"pallas"`` traces with x64
    off even when called from inside :func:`repro.runtime.x64`.  The
    other impls keep the ambient mode.
    """
    return jax.enable_x64(False) if impl == "pallas" else contextlib.nullcontext()


def _order_batch(n_orders: int, tile: int, n: int) -> int:
    """Orders per jit call so (P_b, tile, N) intermediates stay bounded."""
    per_order = tile * n * 8  # float64 worst case
    return max(1, min(n_orders, 4096, _TILE_BYTES_BUDGET // max(per_order, 1)))


# ---------------------------------------------------------------------------
# XLA streaming implementation (shared decode across the order batch)
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("strides", "radix", "k_total", "tile")
)
def _enum_xla(sizes, probs, orders, *, strides, radix, k_total, tile):
    """Exact fused evaluation; ``strides``/``radix`` are static tuples so
    the mixed-radix decode lowers to constant div/mod chains."""
    n = orders.shape[1]
    strides_a = jnp.asarray(strides, jnp.int32)[None, :]
    radix_a = jnp.asarray(radix, jnp.int32)[None, :]
    job_ids = jnp.arange(n, dtype=jnp.int32)[None, :]
    n_tiles = max(1, -(-k_total // tile))

    def tile_fn(carry, t):
        e_succ, e_all = carry
        k = t * tile + jnp.arange(tile, dtype=jnp.int32)
        valid = k < k_total
        s = (k[:, None] // strides_a) % radix_a  # (T, N) on-the-fly decode
        w = jnp.prod(probs[job_ids, s], axis=1) * valid  # Eq. (8)
        d = sizes[job_ids, s]  # (T, N) realized durations
        succ = s == radix_a - 1
        cnt = jnp.sum(succ, axis=1)  # order-invariant success count
        inv_cnt = jnp.where(cnt > 0, 1.0 / jnp.maximum(cnt, 1), 0.0)

        def per_order(order):
            tcum = jnp.cumsum(jnp.take(d, order, axis=1), axis=1)
            tot = jnp.sum(tcum * jnp.take(succ, order, axis=1), axis=1)
            return (
                jnp.dot(w, tot * inv_cnt),  # Eqs. (7)+(9)
                jnp.dot(w, jnp.mean(tcum, axis=1)),
            )

        des, dea = jax.vmap(per_order)(orders)
        return (e_succ + des, e_all + dea), None

    zeros = jnp.zeros((orders.shape[0],), sizes.dtype)
    (e_succ, e_all), _ = jax.lax.scan(
        tile_fn, (zeros, zeros), jnp.arange(n_tiles, dtype=jnp.int32)
    )
    return e_succ, e_all


@functools.partial(jax.jit, static_argnames=("n_samples", "tile"))
def _mc_xla(sizes, cdf, num_stages, orders, key2, *, n_samples, tile):
    """Streamed-MC fused evaluation: per-tile Threefry outcome generation
    with the same inverse-CDF count as the host replay, then the shared
    prefix-sum reduction.  ``key2`` is a (2,) uint32 array (traced, so
    sweeps over seeds do not recompile)."""
    n = orders.shape[1]
    job_ids = jnp.arange(n, dtype=jnp.int32)[None, :]
    n_tiles = max(1, -(-n_samples // tile))
    x1 = jnp.broadcast_to(job_ids, (tile, n)).astype(jnp.uint32)

    def tile_fn(carry, t):
        e_succ, e_all = carry
        k = t * tile + jnp.arange(tile, dtype=jnp.int32)
        x0 = jnp.broadcast_to(k[:, None], (tile, n)).astype(jnp.uint32)
        bits, _ = rng.threefry2x32(jnp, (key2[0], key2[1]), x0, x1)
        u = rng.uniform_from_bits(bits, sizes.dtype)
        s = jnp.minimum(
            jnp.sum(u[:, :, None] >= cdf[None, :, :], axis=2).astype(jnp.int32),
            num_stages[None, :] - 1,
        )
        w = (k < n_samples).astype(sizes.dtype) * (1.0 / n_samples)
        d = sizes[job_ids, s]  # (T, N) realized durations
        succ = s == num_stages[None, :] - 1
        cnt = jnp.sum(succ, axis=1)
        inv_cnt = jnp.where(cnt > 0, 1.0 / jnp.maximum(cnt, 1), 0.0)

        def per_order(order):
            tcum = jnp.cumsum(jnp.take(d, order, axis=1), axis=1)
            tot = jnp.sum(tcum * jnp.take(succ, order, axis=1), axis=1)
            return (
                jnp.dot(w, tot * inv_cnt),
                jnp.dot(w, jnp.mean(tcum, axis=1)),
            )

        des, dea = jax.vmap(per_order)(orders)
        return (e_succ + des, e_all + dea), None

    zeros = jnp.zeros((orders.shape[0],), sizes.dtype)
    (e_succ, e_all), _ = jax.lax.scan(
        tile_fn, (zeros, zeros), jnp.arange(n_tiles, dtype=jnp.int32)
    )
    return e_succ, e_all


@jax.jit
def _outcomes_xla(sizes, num_stages, outcomes, weights, orders):
    """Fused evaluation over an explicit outcome matrix: the duration and
    success gathers happen on-device instead of as host fancy-indexing."""
    n = orders.shape[1]
    job_ids = jnp.arange(n, dtype=jnp.int32)[None, :]
    d = sizes[job_ids, outcomes]  # (K, N)
    succ = outcomes == num_stages[None, :] - 1
    cnt = jnp.sum(succ, axis=1)
    inv_cnt = jnp.where(cnt > 0, 1.0 / jnp.maximum(cnt, 1), 0.0)

    def per_order(order):
        tcum = jnp.cumsum(jnp.take(d, order, axis=1), axis=1)
        tot = jnp.sum(tcum * jnp.take(succ, order, axis=1), axis=1)
        return (
            jnp.dot(weights, tot * inv_cnt),
            jnp.dot(weights, jnp.mean(tcum, axis=1)),
        )

    return jax.vmap(per_order)(orders)


# ---------------------------------------------------------------------------
# Pallas-path input preparation
# ---------------------------------------------------------------------------


def _permuted(arrs, orders_b):
    """Take the job axis of each array along every order in the batch."""
    return [np.take(a, orders_b, axis=0) for a in arrs]


def _tile_outcomes(outcomes, weights):
    """(K, N) -> (N, KT, SUBLANES, LANES) stage tiles + zero-padded weights."""
    k_total, n = outcomes.shape
    bk = K.BLOCK_COMBOS
    nkt = max(1, -(-k_total // bk))
    pad = nkt * bk - k_total
    oc = np.pad(outcomes.astype(np.int32), ((0, pad), (0, 0)))
    wt = np.pad(np.asarray(weights), (0, pad))
    oc_t = oc.T.reshape(n, nkt, K.SUBLANES, K.LANES)
    wt_t = wt.reshape(nkt, K.SUBLANES, K.LANES)
    return oc_t, wt_t


# ---------------------------------------------------------------------------
# Public op
# ---------------------------------------------------------------------------


def sojourn_eval(
    sizes: np.ndarray,  # (N, M) padded cumulative sizes
    probs: np.ndarray,  # (N, M) padded stop probabilities
    num_stages: np.ndarray,  # (N,) stage counts
    orders: np.ndarray,  # (P, N) static orders
    *,
    outcomes: np.ndarray | None = None,  # optional (K, N) explicit outcomes
    weights: np.ndarray | None = None,  # (K,) weights (required with outcomes)
    samples: tuple[int, int] | None = None,  # (seed, n_samples) streamed MC
    impl: Impl = "auto",
) -> tuple[np.ndarray, np.ndarray]:
    """(E[sojourn successful], E[sojourn all]) per order; see module doc.

    When :mod:`repro.obs.profiling` is enabled, each call is timed into
    a ``prof.sojourn_eval.static.<mode>.<impl>.seconds`` span (the
    numpy conversions inside synchronize the device work, so the span
    is end-to-end wall clock).
    """
    impl = resolve_impl(impl)
    mode = "mc" if samples is not None else (
        "enum" if outcomes is None else "outcomes"
    )
    with profiling.span(f"sojourn_eval.static.{mode}.{impl}"), precision_scope(impl):
        return _sojourn_eval(
            sizes, probs, num_stages, orders,
            outcomes=outcomes, weights=weights, samples=samples, impl=impl,
        )


def _sojourn_eval(
    sizes, probs, num_stages, orders, *,
    outcomes=None, weights=None, samples=None, impl="xla",
) -> tuple[np.ndarray, np.ndarray]:
    if samples is not None and outcomes is not None:
        raise ValueError("samples= and outcomes= are mutually exclusive")
    sizes = np.asarray(sizes)
    probs = np.asarray(probs)
    num_stages = np.asarray(num_stages, dtype=np.int64)
    orders = np.asarray(orders, dtype=np.int32)
    n = sizes.shape[0]
    if orders.ndim != 2 or orders.shape[1] != n:
        raise ValueError(f"orders must be (P, {n}); got {orders.shape}")
    strides = mixed_radix_strides(num_stages)
    fdt = compute_dtype(impl)
    sizes_j = jnp.asarray(sizes, fdt)
    probs_j = jnp.asarray(probs, fdt)

    interpret = impl == "interpret"
    e_succ_parts, e_all_parts = [], []
    if samples is not None:
        seed, n_samples = int(samples[0]), int(samples[1])
        if n_samples <= 0:
            raise ValueError(f"n_samples must be positive; got {n_samples}")
        cdf = np.cumsum(probs, axis=1)  # padded stages add 0 mass
        tile = min(
            XLA_TILE, max(K.BLOCK_COMBOS, 1 << (n_samples - 1).bit_length())
        )
        pb = _order_batch(orders.shape[0], tile, n)
        key2 = jnp.asarray(rng.split_seed(seed), jnp.uint32)
        for lo in range(0, orders.shape[0], pb):
            ob = orders[lo : lo + pb]
            if impl == "xla":
                es, ea = _mc_xla(
                    sizes_j,
                    jnp.asarray(cdf, fdt),
                    jnp.asarray(num_stages, jnp.int32),
                    jnp.asarray(ob),
                    key2,
                    n_samples=n_samples,
                    tile=tile,
                )
            else:
                sz_p, cdf_p, rx_p = _permuted(
                    [sizes, cdf, num_stages.astype(np.int32)], ob
                )
                es, ea = K.sojourn_mc(
                    jnp.asarray(sz_p, fdt),
                    jnp.asarray(cdf_p, fdt),
                    jnp.asarray(rx_p),
                    jnp.asarray(ob),
                    jnp.asarray(rng.split_seed(seed), jnp.int32),
                    n_samples,
                    interpret=interpret,
                )
            e_succ_parts.append(np.asarray(es))
            e_all_parts.append(np.asarray(ea))
    elif outcomes is None:
        k_total = int(np.prod(num_stages, dtype=np.int64))
        tile = min(XLA_TILE, max(K.BLOCK_COMBOS, 1 << (k_total - 1).bit_length()))
        pb = _order_batch(orders.shape[0], tile, n)
        for lo in range(0, orders.shape[0], pb):
            ob = orders[lo : lo + pb]
            if impl == "xla":
                es, ea = _enum_xla(
                    sizes_j,
                    probs_j,
                    jnp.asarray(ob),
                    strides=tuple(int(s) for s in strides),
                    radix=tuple(int(r) for r in num_stages),
                    k_total=k_total,
                    tile=tile,
                )
            else:
                sz_p, pr_p, st_p, rx_p = _permuted(
                    [sizes, probs, strides.astype(np.int32),
                     num_stages.astype(np.int32)],
                    ob,
                )
                es, ea = K.sojourn_enum(
                    jnp.asarray(sz_p, fdt),
                    jnp.asarray(pr_p, fdt),
                    jnp.asarray(st_p),
                    jnp.asarray(rx_p),
                    k_total,
                    interpret=interpret,
                )
            e_succ_parts.append(np.asarray(es))
            e_all_parts.append(np.asarray(ea))
    else:
        if weights is None:
            raise ValueError("explicit outcomes need weights")
        outcomes = np.asarray(outcomes, dtype=np.int32)
        if impl != "xla":
            oc_t, wt_t = _tile_outcomes(outcomes, weights)
            oc_j, wt_j = jnp.asarray(oc_t), jnp.asarray(wt_t, fdt)
        else:
            oc_j = jnp.asarray(outcomes)
            wt_j = jnp.asarray(weights, fdt)
        pb = _order_batch(orders.shape[0], outcomes.shape[0], n)
        for lo in range(0, orders.shape[0], pb):
            ob = orders[lo : lo + pb]
            if impl == "xla":
                es, ea = _outcomes_xla(
                    sizes_j,
                    jnp.asarray(num_stages, jnp.int32),
                    oc_j,
                    wt_j,
                    jnp.asarray(ob),
                )
            else:
                sz_p, rx_p = _permuted(
                    [sizes, num_stages.astype(np.int32)], ob
                )
                es, ea = K.sojourn_outcomes(
                    jnp.asarray(sz_p, fdt),
                    jnp.asarray(rx_p),
                    jnp.asarray(ob),
                    oc_j,
                    wt_j,
                    interpret=interpret,
                )
            e_succ_parts.append(np.asarray(es))
            e_all_parts.append(np.asarray(ea))
    return np.concatenate(e_succ_parts), np.concatenate(e_all_parts)
