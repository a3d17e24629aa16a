"""Fused Pallas evaluator for E[sojourn time of successful jobs].

Kernel design note — mapping tiles to the paper's Eqs. (7)-(9)
===============================================================

The paper scores a static order exactly by summing over every outcome
combination ``c = (stage_0, ..., stage_{N-1})`` of which checkpoint each
job stops at:

* **Eq. (8)** — the probability of a combination is the product of the
  per-job stop probabilities, ``P(c) = prod_i p_{i, stage_i}``.
* **Eq. (7)** — given a combination with ``l >= 1`` successful jobs
  (``stage_i = M_i - 1``), the conditional objective is the *mean* of
  the successful jobs' completion times under the order's prefix sums.
* **Eq. (9)** — the expectation is the probability-weighted sum of
  Eq. (7) over all ``K = prod_i M_i`` combinations (``l = 0`` terms
  contribute zero).

The kernel grid is ``(P orders, ceil(K / BLOCK_COMBOS))`` with the
combination axis innermost (sequential on TPU).  Each grid tile owns
``BLOCK_COMBOS = 8 x 128`` combination *indices* shaped as one
``(SUBLANES, LANES)`` VPU tile and, per order position ``pos``:

1. decodes its slice of mixed-radix indices on the fly,
   ``stage = (k // stride_pos) % M_pos`` — the ``(K, N)`` outcome
   matrix of the seed implementation is never materialized anywhere;
2. gathers the realized duration and stop probability from the padded
   ``(N, M)`` size/probability tables via a one-hot select over the
   small stage axis (no vector gather needed on TPU);
3. advances the completion-time prefix sum ``t += d_pos`` (service
   position equals loop position because inputs are pre-permuted by the
   order), accumulating the Eq.-8 weight product ``w *= p`` and the
   Eq.-7 numerator/denominator (``tot += t`` on success, ``cnt += 1``);
4. accumulates ``w * tot / cnt`` — Eq. (9)'s summand — into a VMEM
   scratch accumulator that persists across combination tiles, flushed
   to the per-order output on the last tile.

``ops.sojourn_eval`` fronts the kernels with an ``impl`` dispatch
("pallas" / "interpret" / tiled "xla" streaming fallback for CPU), and
:mod:`repro.core.evaluator` rides it for ``expected_sojourn_static``
and ``optimal_order``.

Dynamic (stage-level) policies — SR / SERPT / conditional-RANK — stream
through the same scheme via :mod:`repro.kernels.sojourn_eval.dynamic`:
each tile decodes its combination indices with the identical mixed-radix
rule, then runs the single-server stage-boundary preemption simulation
*inside the tile*, selecting the minimum conditional index from the
policy's precomputed ``(N, M)`` rank table at every stage completion
(full design note in ``dynamic.py`` and ``docs/dynamic_sojourn_eval.md``).
``evaluator.expected_sojourn_dynamic`` rides it, which lifts exact
SR/SERPT evaluation from the materialized-table cap (2^21) to the same
2^26 streaming bound as static orders.

Beyond the exact cap, both ops take ``samples=(seed, n_samples)`` and
switch to *streaming Monte Carlo*: outcomes are generated inside the
tiles from a counter-based Threefry stream keyed by ``(seed, sample,
job)`` (:mod:`repro.kernels.sojourn_eval.rng`) and an inverse-CDF
search over the per-job stop-probability CDF, so no ``(S, N)`` sample
table is ever materialized and every policy evaluated under one seed
sees the identical outcome sequence (common random numbers; full design
note in ``docs/streaming_mc.md``).
"""

from repro.kernels.sojourn_eval.dynamic import dynamic_sojourn_mc  # noqa: F401

from repro.kernels.sojourn_eval.dynamic import sojourn_eval_dynamic  # noqa: F401
from repro.kernels.sojourn_eval.ops import sojourn_eval  # noqa: F401
