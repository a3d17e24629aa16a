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

Two sources of outcome combinations, mirroring
:mod:`repro.core.evaluator`:

* ``sojourn_eval(...)`` — *exact enumeration*: evaluates all
  ``K = prod(M_i)`` combinations without ever materializing them
  (supports K up to ``repro.core.evaluator.MAX_EXACT_COMBOS``).
* ``sojourn_eval(..., samples=(seed, n_samples))`` — *streaming Monte
  Carlo*: outcomes are generated inside the tiles from the counter-based
  Threefry stream (:mod:`repro.kernels.sojourn_eval.rng`) and an
  inverse-CDF search, so no ``(S, N)`` sample table exists on host or
  device and sample counts are compute-bound rather than
  table-memory-bound.  The stream is keyed by original job id: every
  order/policy evaluated under one seed sees identical outcomes
  (common random numbers), and ``ref.ref_mc_outcomes`` replays the
  stream host-side bitwise for parity.

Both sources feed one scoring body: on the XLA path
:func:`_score_tiles`, on the chip a kernel each (``kernel.py``).

Precision: ``impl="pallas"`` always runs the kernels in float32, since
Mosaic has no 64-bit types; its error against the float64 oracle is
bounded per source by :data:`CHIP_RTOL`.  ``"xla"`` and
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
#: oracle, per source, at the sizes ``chip_smoke.py`` runs.  Shared by
#: that script and the float32 parity tests.  On a TPU v5e the largest
#: errors seen were 4.1e-7 (enum, 9! orders and K = 2**26) and 3.8e-8
#: (mc, 2**20 samples).  "mc" has room for a few samples whose float32
#: uniform lands on the other side of a float32 CDF entry than in
#: float64: each moves the mean by about 1/n_samples.
CHIP_RTOL = {"enum": 1e-6, "mc": 1e-5}

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
    """Orders per jit call so (P_b, tile, N) intermediates stay bounded.

    Governs the XLA paths and ``sojourn_mc``, whose calls hold per-order
    inputs.  The Pallas enumeration kernel streams its orders through
    SMEM a block at a time and scores every order in one call.
    """
    per_order = tile * n * 8  # float64 worst case
    return max(1, min(n_orders, 4096, _TILE_BYTES_BUDGET // max(per_order, 1)))


# ---------------------------------------------------------------------------
# XLA streaming implementation (shared decode across the order batch)
# ---------------------------------------------------------------------------


def _score_tiles(sizes, radix, orders, total, tile, decode):
    """Eqs. (7)+(9) for every order, over ``total`` combinations or samples.

    A ``lax.scan`` over tiles of ``tile`` indices ``k``: ``decode(k)``
    gives their ``(T, N)`` stop stages and ``(T,)`` weights, and indices
    past ``total`` weigh zero.  The gathers and the success count are
    shared across the orders; only the completion-time prefix sums are
    per order.
    """
    job_ids = jnp.arange(orders.shape[1], dtype=jnp.int32)[None, :]

    def tile_fn(carry, t):
        e_succ, e_all = carry
        k = t * tile + jnp.arange(tile, dtype=jnp.int32)
        s, w = decode(k)
        w = w * (k < total)
        d = sizes[job_ids, s]  # (T, N) realized durations
        succ = s == radix - 1
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
    n_tiles = max(1, -(-total // tile))
    (e_succ, e_all), _ = jax.lax.scan(
        tile_fn, (zeros, zeros), jnp.arange(n_tiles, dtype=jnp.int32)
    )
    return e_succ, e_all


@functools.partial(
    jax.jit, static_argnames=("strides", "radix", "k_total", "tile")
)
def _enum_xla(sizes, probs, orders, *, strides, radix, k_total, tile):
    """Exact fused evaluation; ``strides``/``radix`` are static tuples so
    the mixed-radix decode lowers to constant div/mod chains."""
    strides_a = jnp.asarray(strides, jnp.int32)[None, :]
    radix_a = jnp.asarray(radix, jnp.int32)[None, :]
    job_ids = jnp.arange(orders.shape[1], dtype=jnp.int32)[None, :]

    def decode(k):
        s = (k[:, None] // strides_a) % radix_a  # (T, N) on-the-fly decode
        return s, jnp.prod(probs[job_ids, s], axis=1)  # Eq. (8)

    return _score_tiles(sizes, radix_a, orders, k_total, tile, decode)


@functools.partial(jax.jit, static_argnames=("n_samples", "tile"))
def _mc_xla(sizes, cdf, num_stages, orders, key2, *, n_samples, tile):
    """Streamed-MC fused evaluation: per-tile Threefry outcome generation
    with the same inverse-CDF count as the host replay.  ``key2`` is a
    (2,) uint32 array (traced, so sweeps over seeds do not recompile)."""
    n = orders.shape[1]
    radix = num_stages[None, :]
    x1 = jnp.broadcast_to(jnp.arange(n, dtype=jnp.uint32)[None, :], (tile, n))

    def decode(k):
        x0 = jnp.broadcast_to(k[:, None], (tile, n)).astype(jnp.uint32)
        bits, _ = rng.threefry2x32(jnp, (key2[0], key2[1]), x0, x1)
        u = rng.uniform_from_bits(bits, sizes.dtype)
        s = jnp.sum(u[:, :, None] >= cdf[None, :, :], axis=2).astype(jnp.int32)
        return jnp.minimum(s, radix - 1), jnp.full((tile,), 1.0 / n_samples, sizes.dtype)

    return _score_tiles(sizes, radix, orders, n_samples, tile, decode)


# ---------------------------------------------------------------------------
# Pallas-path input preparation
# ---------------------------------------------------------------------------


def _permuted(arrs, orders_b):
    """Take the job axis of each array along every order in the batch."""
    return [np.take(a, orders_b, axis=0) for a in arrs]


# ---------------------------------------------------------------------------
# Public op
# ---------------------------------------------------------------------------


def sojourn_eval(
    sizes: np.ndarray,  # (N, M) padded cumulative sizes
    probs: np.ndarray,  # (N, M) padded stop probabilities
    num_stages: np.ndarray,  # (N,) stage counts
    orders: np.ndarray,  # (P, N) static orders
    *,
    samples: tuple[int, int] | None = None,  # (seed, n_samples) streamed MC
    impl: Impl = "auto",
) -> tuple[np.ndarray, np.ndarray]:
    """(E[sojourn successful], E[sojourn all]) per order; see module doc.

    When :mod:`repro.obs.profiling` is enabled, each call is timed as a
    ``prof.sojourn_eval.static.<mode>.<impl>`` span (the numpy
    conversions inside synchronize the device work, so the span is
    end-to-end wall clock), tiled by the phase spans
    ``prof.op_phase.static.<impl>.{prep,put,call,sync}`` of
    :func:`run_batches`.
    """
    impl = resolve_impl(impl)
    mode = "mc" if samples is not None else "enum"
    with (
        profiling.span(f"sojourn_eval.static.{mode}.{impl}"),
        profiling.phases(f"op_phase.static.{impl}", "prep") as phase,
        precision_scope(impl),
    ):
        return _sojourn_eval(
            sizes, probs, num_stages, orders,
            samples=samples, impl=impl, phase=phase,
        )


def run_batches(phase, items, batch, fdt, shared, per_batch, call):
    """Answer ``items`` (orders or index tables) ``batch`` at a time.

    Each batch runs four phases of ``phase`` (:func:`repro.obs.profiling.phases`):
    ``prep``, the host work of ``per_batch(items_b)``, which returns the
    batch's host arrays; ``put``, their copies to the device (with the
    ``shared`` host arrays every batch uses, in the first batch); ``call``,
    the dispatch of ``call(*shared, *batch arrays)``; ``sync``, the wait
    for its two answers and their copy back.  Answers past a batch's
    number of items (the padding of the static kernel's last order block)
    are dropped.  Float arrays go to the device as ``fdt``, the others
    keep their dtype.
    """

    def put(a):
        return jnp.asarray(a, fdt) if a.dtype.kind == "f" else jnp.asarray(a)

    shared_j = None
    e_succ, e_all = [], []
    for lo in range(0, len(items), batch):
        phase.to("prep")
        items_b = items[lo : lo + batch]
        host = per_batch(items_b)
        phase.to("put")
        if shared_j is None:
            shared_j = [put(a) for a in shared]
        args = [put(a) for a in host]
        phase.to("call")
        es, ea = call(*shared_j, *args)
        del args  # free this batch's inputs before the next batch is put
        phase.to("sync")
        e_succ.append(np.asarray(es).reshape(-1)[: len(items_b)])
        e_all.append(np.asarray(ea).reshape(-1)[: len(items_b)])
    return np.concatenate(e_succ), np.concatenate(e_all)


def _sojourn_eval(
    sizes, probs, num_stages, orders, *,
    samples=None, impl="xla", phase,
) -> tuple[np.ndarray, np.ndarray]:
    sizes = np.asarray(sizes)
    probs = np.asarray(probs)
    num_stages = np.asarray(num_stages, dtype=np.int64)
    orders = np.asarray(orders, dtype=np.int32)
    n = sizes.shape[0]
    if orders.ndim != 2 or orders.shape[1] != n:
        raise ValueError(f"orders must be (P, {n}); got {orders.shape}")
    strides = mixed_radix_strides(num_stages)
    radix = num_stages.astype(np.int32)
    fdt = compute_dtype(impl)
    interpret = impl == "interpret"
    if samples is not None:
        seed, n_samples = int(samples[0]), int(samples[1])
        if n_samples <= 0:
            raise ValueError(f"n_samples must be positive; got {n_samples}")
        cdf = np.cumsum(probs, axis=1)  # padded stages add 0 mass
        tile = min(
            XLA_TILE, max(K.BLOCK_COMBOS, 1 << (n_samples - 1).bit_length())
        )
        pb = _order_batch(orders.shape[0], tile, n)
        if impl == "xla":
            shared = [sizes, cdf, radix, np.asarray(rng.split_seed(seed), np.uint32)]

            def per_batch(ob):
                return [ob]

            def call(sz, cd, rx, key2, ob):
                return _mc_xla(sz, cd, rx, ob, key2, n_samples=n_samples, tile=tile)
        else:
            shared = []
            key = np.asarray(rng.split_seed(seed), np.int32)

            def per_batch(ob):
                return [*_permuted([sizes, cdf, radix], ob), ob, key]

            def call(sz, cd, rx, ob, k):
                return K.sojourn_mc(sz, cd, rx, ob, k, n_samples, interpret=interpret)
    else:
        k_total = int(np.prod(num_stages, dtype=np.int64))
        if impl == "xla":
            tile = min(XLA_TILE, max(K.BLOCK_COMBOS, 1 << (k_total - 1).bit_length()))
            pb = _order_batch(orders.shape[0], tile, n)
            shared = [sizes, probs]

            def per_batch(ob):
                return [ob]

            def call(sz, pr, ob):
                return _enum_xla(
                    sz, pr, ob,
                    strides=tuple(int(s) for s in strides),
                    radix=tuple(int(r) for r in num_stages),
                    k_total=k_total,
                    tile=tile,
                )
        else:
            # One call scores every order: the kernel streams them through
            # SMEM a block at a time.  The job group's tables go once, in
            # the dtype the kernel reads.
            pb = len(orders)
            shared = [
                np.stack([sizes, probs]).astype(fdt),
                np.stack([strides, radix]).astype(np.int32),
            ]

            def per_batch(ob):
                return [K.enum_blocked_orders(ob, k_total)]

            def call(tables, ints, blocked):
                profiling.count("sojourn_enum.calls")
                profiling.count("sojourn_enum.orders", pb)
                blocks, tiles = K.enum_grid(pb, k_total)
                profiling.count("sojourn_enum.order_blocks", blocks)
                profiling.count("sojourn_enum.grid_steps", blocks * tiles)
                return K.sojourn_enum(tables, ints, blocked, k_total, pb, interpret=interpret)
    return run_batches(phase, orders, pb, fdt, shared, per_batch, call)
