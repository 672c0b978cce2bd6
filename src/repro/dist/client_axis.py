"""The client axis: intra-cell [N, ...] aggregation sharded with psum.

FedChain's round body is the paper's local-phase structure: N independent
per-client computations joined ONLY by a server aggregation. On a
``('client',)`` (or ``('grid', 'client')``) mesh this maps to shard_map over
the client rows — each device computes ITS clients and runs the Pallas
aggregation kernels (``chain_aggregate`` / ``weighted_mean_over_clients``,
``repro.kernels``) on its LOCAL rows; one ``jax.lax.psum`` over the
``client`` mesh axis completes the mean. That psum is the grouped-collective
formulation the old ``launch/fedchain_shardmap.py`` scaffold sketched with
``axis_index_groups`` (now rebased here): no collective crosses the client
axis except the aggregation itself.

Numerics: summing per-shard partial aggregates reorders the float reduction
over clients, so the client axis is equivalent to the single-device mean to
float tolerance, not bitwise — use the grid axis (``dist.grid``) when
bit-reproducibility matters. Bits accounting is unaffected either way: the
wire cost of a round is a closed form over the mask and parameter shapes
(``repro.comm``), independent of how the server-side mean is computed.

Scope: these are the aggregation-layer primitives plus a full-participation
client-sharded round (``sgd_round_client_sharded``) demonstrating the
local-compute → psum-join structure end to end. The sweep engines do not
route through this axis by default — grid cells are embarrassingly parallel
and pay zero collectives, so the grid axis is the production path; the
client axis is for the regime where ONE cell's clients outgrow a device.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import tree_math as tm
from repro.dist import mesh as mesh_lib


def _client_axis_size(mesh):
    n = mesh_lib.client_size(mesh)
    if n <= 1 and "client" not in mesh.axis_names:
        raise ValueError(
            f"mesh {mesh.axis_names} has no 'client' axis — build one with "
            f"dist.make_grid_client_mesh (or a 1-D ('client',) mesh)")
    return max(n, 1)


def sharded_client_mean(mesh, stacked, weights=None):
    """meanᵢ(wᵢ·tᵢ) over a [N, ...] client pytree, rows sharded over the
    ``client`` mesh axis.

    Each shard ravels its local rows leaf-wise to the kernel boundary and
    runs the Pallas ``weighted_mean_over_clients`` on them (exactly like the
    single-device ``algorithms.base.weighted_client_mean``); the partial
    means are completed by one psum: with K shards of N/K rows each, the
    mean over N is (1/K)·psum(local mean). ``weights`` defaults to all-ones
    (the plain client mean). N must divide by the client-axis size.
    """
    from repro.kernels.compress import ops as compress_ops

    k_shards = _client_axis_size(mesh)
    n = jax.tree.leaves(stacked)[0].shape[0]
    if n % k_shards:
        raise ValueError(f"client rows {n} must divide the client axis "
                         f"({k_shards} shards)")
    if weights is None:
        weights = jnp.ones((n,), jnp.float32)

    def body(rows, w):
        local = jax.tree.map(
            lambda r: compress_ops.weighted_mean_over_clients(r, w),
            tm.tree_ravel_rows(rows))
        total = jax.tree.map(
            lambda m: jax.lax.psum(m, "client") / k_shards, local)
        return jax.tree.map(
            lambda m, r: m.reshape(r.shape[1:]), total, rows)

    fn = jax.shard_map(
        body, mesh=mesh, check_vma=False,
        in_specs=(jax.tree.map(lambda _: P("client"), stacked),
                  P("client")),
        out_specs=jax.tree.map(lambda _: P(), stacked))
    return fn(stacked, weights)


def sharded_chain_aggregate(mesh, x, g, c_i, c, *, lr: float, weights=None):
    """The fused FedChain server update with client rows sharded:

        out = x − lr·(Σᵢ wᵢ·(gᵢ − cᵢ) + c)

    Each shard runs the Pallas ``chain_aggregate`` kernel on its local rows
    (server variate 0, so the shard output is x − lr·Σ_local); the partial
    updates are joined by one psum over the ``client`` axis and the server
    variate ``c`` is applied once. ``weights`` defaults to uniform 1/S over
    the GLOBAL rows, matching the single-device kernel's default.
    """
    from repro.kernels.aggregate import ops as agg_ops

    k_shards = _client_axis_size(mesh)
    s = g.shape[0]
    if s % k_shards:
        raise ValueError(f"client rows {s} must divide the client axis "
                         f"({k_shards} shards)")
    if weights is None:
        weights = jnp.full((s,), 1.0 / s, jnp.float32)

    def body(g_loc, ci_loc, w_loc):
        # zeros, not zeros_like: x is closed over from outside the
        # shard_map and may carry a sharding of the enclosing mesh
        partial = agg_ops.chain_aggregate(
            x, g_loc, ci_loc, jnp.zeros(x.shape, x.dtype), weights=w_loc,
            lr=lr)
        delta = jax.lax.psum(partial - x, "client")  # −lr·Σ wᵢ(gᵢ−cᵢ)
        return x + delta - lr * c.astype(x.dtype)

    fn = jax.shard_map(
        body, mesh=mesh, check_vma=False,
        in_specs=(P("client"), P("client"), P("client")),
        out_specs=P())
    return fn(g, c_i, weights)


def sgd_round_client_sharded(mesh, problem, x, eta, key, *, k: int):
    """One full-participation Algo-2 round with the client dimension
    sharded: per-shard ``grad_k`` local phases, per-shard Pallas partial
    aggregation, one psum join — the paper's local-computation/aggregation
    split as mesh collectives. Returns the updated server iterate
    (equivalent to the single-device round's ``state.x`` to float
    tolerance; the client permutation and oracle keys are identical).
    """
    from repro.core.algorithms import base

    spec = problem if getattr(problem, "is_problem_spec", False) else None
    if spec is None:
        raise TypeError("sgd_round_client_sharded needs a ProblemSpec")
    n = spec.num_clients
    k_shards = _client_axis_size(mesh)
    if n % k_shards:
        raise ValueError(f"num_clients {n} must divide the client axis "
                         f"({k_shards} shards)")
    k_sample, k_grad = jax.random.split(key)
    cids = base.sample_clients(k_sample, n, n)
    keys = jax.random.split(k_grad, n * k).reshape(n, k, -1)
    weights = jnp.full((n,), eta / n, jnp.float32)

    def body(cids_loc, keys_loc, w_loc):
        # the local phase: this shard's clients compute their K-sample
        # gradients with the SAME per-row keys the single-device round uses
        g_loc = base.grad_k(spec, x, cids_loc, None, k, keys=keys_loc)
        partial = _partial_aggregate(x, g_loc, w_loc)
        return x + jax.lax.psum(partial - x, "client")

    def _partial_aggregate(x_, g_loc, w_loc):
        from repro.kernels.aggregate import ops as agg_ops

        return agg_ops.chain_aggregate(
            x_, g_loc, jnp.zeros_like(g_loc), jnp.zeros(x_.shape, x_.dtype),
            weights=w_loc, lr=1.0)

    fn = jax.shard_map(
        body, mesh=mesh, check_vma=False,
        in_specs=(P("client"), P("client"), P("client")),
        out_specs=P())
    return fn(cids, keys, weights)
