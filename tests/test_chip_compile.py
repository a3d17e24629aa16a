"""Compile the four sojourn-evaluator Pallas kernels for a TPU v5e.

Nothing runs: the TPU compiler installed with JAX compiles for a chip
that is described, not attached.  This catches what interpret mode
cannot (block shapes Mosaic refuses, SMEM overflow, unsupported casts,
64-bit types) at the sizes the evaluator really uses, in float32, M = 2:
the static enumeration kernel's one call of all 8! and 9! orders (N = 8
and N = 9, OPTIMAL's searches) and of 4,096 orders at N = 21
(K = 2^21); the streamed-MC kernel's batch of 4,096 orders and the
dynamic lockstep at 1 and 3 servers at N = 9 and N = 21; and the static
kernel at the stage sweep's N = 5, M = 8 (K = 2^15, 120 orders and 1).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""

import importlib.util
import math
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.sojourn_eval import dynamic as D
from repro.kernels.sojourn_eval import kernel as K
from repro.kernels.sojourn_eval.ops import precision_scope
from repro.runtime import x64

# The chip benchmark's trace reader (a module of benchmarks/chip, not a package).
_XPLANE = Path(__file__).resolve().parents[1] / "benchmarks" / "chip" / "xplane.py"
_spec = importlib.util.spec_from_file_location("chip_xplane", _XPLANE)
xplane = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(xplane)

ORDERS = 4096  # ops._order_batch's cap: sojourn_mc's real batch
#: Orders of one static enumeration call: OPTIMAL's N! at N = 8 and 9.
ENUM_ORDERS = {8: math.factorial(8), 9: math.factorial(9), 21: ORDERS}
POLICIES = 2
M = 2
SAMPLES = 1 << 20


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A TPU program written to the persistent cache cannot be read back
    # without a chip; keep these compiles out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    # The evaluator calls the op inside the x64 scope, and the op calls the
    # kernels inside its own precision scope: compile them the same way.
    with x64(), precision_scope("pallas"):
        hlo = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


def _static_shapes(chip, n):
    f32 = jax.ShapeDtypeStruct((ORDERS, n, M), jnp.float32, sharding=chip)
    i32 = jax.ShapeDtypeStruct((ORDERS, n), jnp.int32, sharding=chip)
    return f32, i32


def _enum_shapes(chip, n, m, p):
    """Shapes of :func:`K.sojourn_enum`'s arguments for ``p`` orders."""
    blocks, _ = K.enum_grid(p, m**n)
    block, _ = K.enum_order_block(p, m**n)
    return (
        jax.ShapeDtypeStruct((2, n, m), jnp.float32, sharding=chip),
        jax.ShapeDtypeStruct((2, n), jnp.int32, sharding=chip),
        jax.ShapeDtypeStruct((blocks, 1, block * n), jnp.int32, sharding=chip),
    )


@pytest.mark.parametrize("n", sorted(ENUM_ORDERS))
def test_sojourn_enum_compiles(one_chip, n):
    p = ENUM_ORDERS[n]
    shapes = _enum_shapes(one_chip, n, M, p)
    hlo = _compile(lambda t, i, o: K.sojourn_enum(t, i, o, M**n, p), *shapes)
    # The chip benchmark finds the kernel in the profiler trace by this
    # instruction's name (kernel_ms_per_trial.static_enum, static_enum_roofline).
    calls = [
        line.strip().removeprefix("ROOT ")
        for line in hlo.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]
    assert [xplane.op_name(c) for c in calls] == ["sojourn_enum"]


@pytest.mark.parametrize("p", (120, 1))
def test_sojourn_enum_compiles_eight_stages(one_chip, p):
    """Table XIV's cell: N = 5, M = 8, K = 8**5 over 32 combination tiles;
    OPTIMAL's 120 orders (four blocks of 30 with Kahan tiles) and RANK's one."""
    n, m = 5, 8
    _compile(lambda t, i, o: K.sojourn_enum(t, i, o, m**n, p), *_enum_shapes(one_chip, n, m, p))


@pytest.mark.parametrize("n", (9, 21))
def test_sojourn_mc_compiles(one_chip, n):
    f32, i32 = _static_shapes(one_chip, n)
    key = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=one_chip)
    _compile(
        lambda s, c, r, o, k: K.sojourn_mc(s, c, r, o, k, SAMPLES), f32, f32, i32, i32, key
    )


def _dynamic_shapes(chip, n):
    table = jax.ShapeDtypeStruct((n, M), jnp.float32, sharding=chip)
    idx = jax.ShapeDtypeStruct((POLICIES, n, M), jnp.float32, sharding=chip)
    vec = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=chip)
    return table, idx, vec


@pytest.mark.parametrize("n_servers", (1, 3))
@pytest.mark.parametrize("n", (9, 21))
def test_dynamic_sojourn_enum_compiles(one_chip, n, n_servers):
    table, idx, vec = _dynamic_shapes(one_chip, n)

    def fn(p, d, i, st, r):
        return D.dynamic_sojourn_enum(p, d, i, st, r, M**n, n * M, n_servers=n_servers)

    _compile(fn, table, table, idx, vec, vec)


@pytest.mark.parametrize("n_servers", (1, 3))
@pytest.mark.parametrize("n", (9, 21))
def test_dynamic_sojourn_mc_compiles(one_chip, n, n_servers):
    table, idx, vec = _dynamic_shapes(one_chip, n)

    key = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=one_chip)

    def fn(c, d, i, r, k):
        return D.dynamic_sojourn_mc(c, d, i, r, k, SAMPLES, n * M, n_servers=n_servers)

    _compile(fn, table, table, idx, vec, key)
