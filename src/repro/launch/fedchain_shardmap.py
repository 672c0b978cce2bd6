"""FedChain local phase via shard_map + grouped collectives.

.. note:: REBASED onto the distributed sweep subsystem (``repro.dist``).
   This module predates ``repro.dist`` and survives as the grouped-
   collective formulation for *model-training* meshes without a dedicated
   client axis; ``repro.dist.client_axis`` is the maintained client-axis
   layer (per-shard Pallas aggregation + one psum join) and
   ``repro.dist.grid`` is the production path for experiment grids.

The pjit path (`launch.fedchain`) gives each client group its own parameter
replica along a mesh axis. This module is the alternative single-pod
formulation promised in DESIGN.md §2: clients are CONTIGUOUS SUBGROUPS of the
data axis, and the local phase's gradient all-reduce uses
``jax.lax.psum(..., axis_index_groups=...)`` so the reduction never leaves a
client group — the grouped-collective realization of FedAvg's inner loop on a
mesh without a dedicated client axis.

Works on any (data, model) mesh where ``data % clients == 0``. Parameters are
data-axis-replicated per standard DP; during the local phase each subgroup's
copy evolves independently (they diverge across subgroups and re-merge at the
round boundary), which shard_map expresses directly because parameters are
per-device values inside the mapped function.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import tree_math as tm


def client_groups(data_size: int, clients: int):
    """axis_index_groups: contiguous subgroups of the data axis."""
    assert data_size % clients == 0
    per = data_size // clients
    return [list(range(c * per, (c + 1) * per)) for c in range(clients)]


def make_grouped_local_steps(
    loss_fn: Callable,  # (params, batch) -> scalar loss
    *,
    mesh,
    clients: int,
    lr: float,
    steps: int,
):
    """Returns a shard_map-ed function
        (params, batches [steps, local_batch, ...]) -> (params, mean_loss)
    where gradient reductions use axis_index_groups over 'data' — a local
    step emits NO collective that crosses a client-group boundary.

    Inside the mapped function params are per-device; model-axis reductions
    (tensor parallelism) still span the full 'model' axis.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    groups = client_groups(sizes["data"], clients)

    def local_steps(params, batches):
        # per-device shards; batch sharded over data, params replicated
        def one_step(p, batch):
            loss, grads = jax.value_and_grad(loss_fn)(p, batch)
            # grouped data-parallel gradient mean: stays inside the client
            grads = jax.tree.map(
                lambda g: jax.lax.pmean(
                    g, axis_name="data", axis_index_groups=groups),
                grads)
            # model-axis reduction for any partial grads (TP) spans 'model'
            p = tm.tree_axpy(-lr, grads, p)
            loss = jax.lax.pmean(loss, axis_name="data",
                                 axis_index_groups=groups)
            return p, loss

        def body(p, batch):
            p, loss = one_step(p, batch)
            return p, loss

        params, losses = jax.lax.scan(body, params, batches)
        return params, jnp.mean(losses)

    return jax.shard_map(
        local_steps,
        mesh=mesh,
        check_vma=False,
        in_specs=(P(), P(None, "data")),
        out_specs=(P(), P()),
    )


def make_grouped_sync(*, mesh, clients: int):
    """Round boundary: average the (diverged) per-group parameter copies —
    one all-reduce over the FULL data axis (the only cross-client collective)."""

    def sync(params):
        return jax.tree.map(
            lambda p: jax.lax.pmean(p, axis_name="data"), params)

    return jax.shard_map(sync, mesh=mesh, in_specs=(P(),), out_specs=P(),
                         check_vma=False)


def run_grouped_fedavg_round(
    loss_fn, params, batches, *, mesh, clients: int, lr: float, steps: int,
    server_lr: float = 1.0,
):
    """One full FedAvg round: grouped local steps then the cross-group merge."""
    local = make_grouped_local_steps(
        loss_fn, mesh=mesh, clients=clients, lr=lr, steps=steps)
    sync = make_grouped_sync(mesh=mesh, clients=clients)
    new_params, loss = local(params, batches)
    merged = sync(new_params)
    if server_lr != 1.0:
        merged = jax.tree.map(
            lambda old, new: ((1 - server_lr) * old.astype(jnp.float32)
                              + server_lr * new.astype(jnp.float32)).astype(new.dtype),
            params, merged)
    return merged, loss
