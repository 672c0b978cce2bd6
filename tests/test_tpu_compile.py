"""The sweep-path Pallas kernels compile under Mosaic for a v5e chip.

Compiles each aggregation/compress kernel for a described (not attached)
v5e chip at the shapes the sweep engine gives it: vmapped over grid cells,
then [S, D] client rows, plus one bare large call. Mosaic refuses illegal
block shapes and over-budget VMEM here, at no chip time. The topology is
described inside a fixture, never at import: only one process may load the
TPU library at a time.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.aggregate.aggregate import (aggregate_apply,
                                               chain_aggregate,
                                               mean_over_clients)
from repro.kernels.compress.compress import (qsgd_dequantize,
                                             weighted_mean_over_clients)

# (batch over cells or None for a bare call, S client rows, D width):
# logreg d=784 and the 784-200-10 MLP (159,010 params) over 32 cells at
# cohort 10, a 256-client cohort over 8 cells, and one [64, 2^20] call
SHAPES = [(32, 10, 784), (32, 10, 159010), (8, 256, 1 << 16),
          (None, 64, 1 << 20)]

# kernel name → (call, operand shapes from (S, D))
KERNELS = {
    "chain_aggregate": (
        lambda x, g, ci, c, w: chain_aggregate(x, g, ci, c, w, lr=1.0),
        lambda s, d: [(d,), (s, d), (s, d), (d,), (s,)]),
    "aggregate_apply": (
        aggregate_apply,
        lambda s, d: [(d,), (s, d), (s, d), (s, d), (s, d), (s,), (s,)]),
    "mean_over_clients": (
        mean_over_clients, lambda s, d: [(s, d)]),
    "qsgd_dequantize": (
        qsgd_dequantize, lambda s, d: [(s, d), (s, d), (s,), ()]),
    "weighted_mean_over_clients": (
        weighted_mean_over_clients, lambda s, d: [(s, d), (s,)]),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("batch,s,d", SHAPES,
                         ids=lambda v: "bare" if v is None else str(v))
@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, batch, s, d, one_chip):
    call, shapes = KERNELS[name]
    fn = call if batch is None else jax.vmap(call)
    lead = () if batch is None else (batch,)
    args = [jax.ShapeDtypeStruct(lead + shape, jnp.float32, sharding=one_chip)
            for shape in shapes(s, d)]
    compiled = jax.jit(fn).lower(*args).compile()
    mosaic = [line for line in compiled.as_text().splitlines()
              if 'custom_call_target="tpu_custom_call"' in line]
    assert any(name in line for line in mosaic), (
        f"{name} was not compiled as a Mosaic kernel")
