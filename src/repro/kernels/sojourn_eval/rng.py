"""Splittable counter-based RNG shared by every streaming-MC path.

One Threefry-2x32 block (Salmon et al., Random123; the same generator
family JAX's default PRNG uses) written against a generic array
namespace ``xp`` so the *identical* integer arithmetic runs

* host-side in NumPy (the ``ref.py`` oracle replay and
  ``evaluator.sample_outcomes``-style parity tests),
* in the jitted XLA fallbacks (``jnp`` under ``lax.scan``), and
* inside the Pallas tiles (``jnp`` on ``(SUBLANES, LANES)`` registers —
  only elementwise uint32 add/xor/shift, all Mosaic-supported).

Because all three paths execute the same uint32 recurrence on the same
``(sample_index, job_index)`` counters under the same key, the outcome
streams agree *bitwise*: a Monte-Carlo sweep never materializes an
``(S, N)`` sample table on device, yet the host oracle can replay any
slice of the stream exactly, and two policies evaluated under one seed
see identical outcome sequences (common random numbers).

Counter layout: ``x0 = sample_index``, ``x1 = job_index`` (each a full
32-bit word, so streams of 2**31+ samples never collide), keyed by the
two 31-bit halves of a user seed (31 bits so the words round-trip
through int32 SMEM scalars on TPU).  The first output word, scaled by
``2**-32``, is the per-(sample, job) uniform; an inverse-CDF count over
the padded per-job CDF turns it into a stop-stage outcome exactly as
:func:`repro.core.evaluator.sample_outcomes` does.
"""

from __future__ import annotations

import numpy as np

__all__ = [
    "split_seed",
    "threefry2x32",
    "uniform_from_bits",
    "host_uniforms",
    "host_outcomes",
]

#: Threefry-2x32 rotation schedule (Random123), alternating per 4-round group.
_ROT_A = (13, 15, 26, 6)
_ROT_B = (17, 29, 16, 24)
#: Key-schedule parity constant for Threefry-32.
_PARITY = 0x1BD11BDA

MAX_SEED = 1 << 62


def split_seed(seed: int) -> tuple[int, int]:
    """Split a 62-bit seed into two 31-bit key words (int32-safe)."""
    if not 0 <= seed < MAX_SEED:
        raise ValueError(f"seed must be in [0, 2**62); got {seed}")
    return seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF


def _rotl(xp, x, r: int):
    return (x << xp.uint32(r)) | (x >> xp.uint32(32 - r))


def threefry2x32(xp, key: tuple, x0, x1):
    """One 20-round Threefry-2x32 block; uint32 in, (uint32, uint32) out.

    ``xp`` is ``numpy`` or ``jax.numpy``; ``key`` is a pair of uint32
    scalars (or 0-d arrays) and ``x0``/``x1`` uint32 arrays of any
    (broadcastable) shape.
    """
    k0, k1 = (xp.uint32(key[0]), xp.uint32(key[1]))
    ks2 = k0 ^ k1 ^ xp.uint32(_PARITY)
    x0 = x0 + k0
    x1 = x1 + k1
    subkeys = ((k1, ks2), (ks2, k0), (k0, k1), (k1, ks2), (ks2, k0))
    rots = (_ROT_A, _ROT_B, _ROT_A, _ROT_B, _ROT_A)
    for i, (rot4, (ka, kb)) in enumerate(zip(rots, subkeys)):
        for r in rot4:
            x0 = x0 + x1
            x1 = _rotl(xp, x1, r)
            x1 = x1 ^ x0
        x0 = x0 + ka
        x1 = (x1 + kb) + xp.uint32(i + 1)
    return x0, x1


def uniform_from_bits(bits, dtype):
    """uint32 bits -> uniform in [0, 1).  Exact in float64 (bits < 2**32
    times a power of two), so comparisons against a shared CDF are
    reproducible bit-for-bit across NumPy / XLA / Pallas.

    The word is converted as two 16-bit halves because Mosaic has no
    uint32 -> float cast.  Both halves are exact in any float dtype and
    the sum rounds once, so the result equals ``bits * 2**-32`` rounded
    to ``dtype``, as a direct cast would give.
    """
    hi = (bits >> 16).astype(np.int32)
    lo = (bits & 0xFFFF).astype(np.int32)
    return hi.astype(dtype) * 2.0**-16 + lo.astype(dtype) * 2.0**-32


# ---------------------------------------------------------------------------
# Host-side replay (the ref.py oracle and parity tests ride these)
# ---------------------------------------------------------------------------


def host_uniforms(
    seed: int, sample_lo: int, n_samples: int, n_jobs: int
) -> np.ndarray:
    """(S, N) float64 uniforms for samples [sample_lo, sample_lo + S)."""
    k0, k1 = split_seed(seed)
    t = np.arange(sample_lo, sample_lo + n_samples, dtype=np.int64)
    x0 = np.broadcast_to(t[:, None], (n_samples, n_jobs)).astype(np.uint32)
    x1 = np.broadcast_to(
        np.arange(n_jobs, dtype=np.int64)[None, :], (n_samples, n_jobs)
    ).astype(np.uint32)
    bits, _ = threefry2x32(np, (k0, k1), x0, x1)
    return uniform_from_bits(bits, np.float64)


def host_outcomes(
    seed: int, n_samples: int, probs: np.ndarray, num_stages: np.ndarray
) -> np.ndarray:
    """(S, N) int32 stop-stage outcomes: the dense replay of the stream.

    Inverse-CDF count over ``cumsum(probs)`` with the same comparison
    direction (``u >= cdf``) and clamp as the in-kernel search, so the
    result is bitwise identical to what the streaming evaluators decode.
    """
    probs = np.asarray(probs, dtype=np.float64)
    num_stages = np.asarray(num_stages)
    cdf = np.cumsum(probs, axis=1)  # padded stages add 0 mass
    u = host_uniforms(seed, 0, n_samples, probs.shape[0])
    outcomes = np.sum(u[:, :, None] >= cdf[None, :, :], axis=2)
    return np.minimum(outcomes, num_stages[None, :] - 1).astype(np.int32)
