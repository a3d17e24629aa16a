"""Compile the five sojourn-evaluator Pallas kernels for a TPU v5e.

Nothing runs: the TPU compiler installed with JAX compiles for a chip
that is described, not attached.  This catches what interpret mode
cannot (block shapes Mosaic refuses, SMEM overflow, unsupported casts,
64-bit types) at the sizes the evaluator really uses: the op's batch of
4,096 orders, float32, N = 9 (OPTIMAL's search) and N = 21 (K = 2^21),
M = 2, and the dynamic lockstep at 1 and 3 servers.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.sojourn_eval import dynamic as D
from repro.kernels.sojourn_eval import kernel as K
from repro.kernels.sojourn_eval.ops import precision_scope
from repro.runtime import x64

ORDERS = 4096  # ops._order_batch's cap: the op's real batch
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


def _static_shapes(chip, n):
    f32 = jax.ShapeDtypeStruct((ORDERS, n, M), jnp.float32, sharding=chip)
    i32 = jax.ShapeDtypeStruct((ORDERS, n), jnp.int32, sharding=chip)
    return f32, i32


@pytest.mark.parametrize("n", (9, 21))
def test_sojourn_enum_compiles(one_chip, n):
    f32, i32 = _static_shapes(one_chip, n)
    _compile(lambda s, p, st, r: K.sojourn_enum(s, p, st, r, M**n), f32, f32, i32, i32)


@pytest.mark.parametrize("n", (9, 21))
def test_sojourn_outcomes_compiles(one_chip, n):
    f32, i32 = _static_shapes(one_chip, n)
    nkt = 4
    oc = jax.ShapeDtypeStruct((n, nkt, K.SUBLANES, K.LANES), jnp.int32, sharding=one_chip)
    wt = jax.ShapeDtypeStruct((nkt, K.SUBLANES, K.LANES), jnp.float32, sharding=one_chip)
    _compile(K.sojourn_outcomes, f32, i32, i32, oc, wt)


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
