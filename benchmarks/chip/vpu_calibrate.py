"""Measure one TPU chip's 32-bit vector (VPU) operation rate.

The scheduler's kernels do 32-bit elementwise work on the vector unit,
and the TPU v5e publishes no peak for it.  This script measures one:
a Pallas kernel runs ``chains`` independent dependency chains over
(8, 128) float32 vector registers; each chain step is an add, a compare
and a select (three 32-bit operations on each of the 1,024 elements).
Several chain counts (unroll widths) are tried, each timed on the host
clock over at least a quarter of a second after a compile run, and the
best rate is the measured peak recorded in ``peaks.json``.

Usage, from the repository root, on a machine with the chip::

    python3 benchmarks/chip/vpu_calibrate.py

Prints one JSON line per width and a last line with the best rate.
"""

from __future__ import annotations

import functools
import json
import sys
import time

SUBLANES, LANES = 8, 128
OPS_PER_STEP = 3  # add, compare, select
ITERS = 4096  # chain steps per grid step
TARGET_OPS = 4e12  # operations per timed call: about a second at 4 TOP/s
WIDTHS = (1, 2, 4, 8, 16, 32)


def _chains_kernel(x_ref, o_ref, *, chains: int, iters: int):
    import jax
    import jax.numpy as jnp

    c = x_ref[...]
    init = tuple(c + float(i) for i in range(chains))

    def body(_, accs):
        out = []
        for a in accs:
            a = a + c
            a = jnp.where(a > 1e30, c, a)
            out.append(a)
        return tuple(out)

    accs = jax.lax.fori_loop(0, iters, body, init)
    total = accs[0]
    for a in accs[1:]:
        total = total + a
    o_ref[0] = total


def build(chains: int, grid: int, iters: int = ITERS):
    """The jitted calibration call for one chain count; returns ``(fn, ops)``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    kernel = functools.partial(_chains_kernel, chains=chains, iters=iters)
    call = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((SUBLANES, LANES), lambda g: (0, 0))],
        out_specs=pl.BlockSpec((1, SUBLANES, LANES), lambda g: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((grid, SUBLANES, LANES), jnp.float32),
        name="vpu_chains",
    )
    ops = float(grid) * iters * chains * OPS_PER_STEP * SUBLANES * LANES
    return jax.jit(call), ops


def measure(chains: int) -> dict:
    import jax
    import jax.numpy as jnp

    per_step = ITERS * chains * OPS_PER_STEP * SUBLANES * LANES
    grid = max(1, int(TARGET_OPS // per_step))
    fn, ops = build(chains, grid)
    x = jnp.full((SUBLANES, LANES), 1e-3, jnp.float32)
    jax.block_until_ready(fn(x))  # compile
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(x))
        best = min(best, time.perf_counter() - t0)
    return {"chains": chains, "grid": grid, "ops": ops, "seconds": best,
            "ops_per_s": ops / best}


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"vpu_calibrate: the first device is {dev.platform!r}, not a TPU",
              file=sys.stderr)
        return 1
    rows = [measure(c) for c in WIDTHS]
    for row in rows:
        print(json.dumps(row), flush=True)
    best = max(rows, key=lambda r: r["ops_per_s"])
    print(json.dumps({"device_kind": dev.device_kind, "vpu_ops_per_s": best["ops_per_s"],
                      "chains": best["chains"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
