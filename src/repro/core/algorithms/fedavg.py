"""FedAvg — the paper's Algorithm 4 (local-update method).

The paper's parametrization splits the per-round oracle budget K into √K
outer local steps, each using a √K-sample-averaged stochastic gradient. We
expose (local_steps, inner_batch) directly and provide ``from_k`` for the
paper's √K×√K convention.

Server update: x^{r+1} = x^r − server_lr · mean_i Σ_k η·g_{i,k}
             = (1 − server_lr)·x^r + server_lr · mean_i x_{i,final}
(the paper uses server_lr = 1, i.e. plain iterate averaging).

Comm-aware: the uplink payload is the local iterate delta y_i − x (the wire
format of local-update methods); the server reconstructs x + C(y_i − x) and
averages over the round's participation mask.
"""
from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import tree_math as tm
from repro.core.algorithms import base
from repro.obs.profile import scope


class FedAvgState(NamedTuple):
    x: object
    eta: jnp.ndarray
    r: jnp.ndarray
    comm: Optional[object] = None


@dataclasses.dataclass(frozen=True)
class FedAvg(base.FederatedAlgorithm):
    local_steps: int = 4  # √K in the paper
    inner_batch: int = 4  # gradient samples averaged per local step (√K)
    server_lr: float = 1.0
    name: str = "fedavg"

    @classmethod
    def from_k(cls, k: int, **kw):
        root = max(1, int(round(math.sqrt(k))))
        return cls(k=k, local_steps=root, inner_batch=root, **kw)

    def _local(self, problem, x0, cid, key, eta):
        """Local SGD steps on client ``cid``; returns the final local iterate."""

        def step(carry, k_step):
            y = carry
            ks = jax.random.split(k_step, self.inner_batch)
            gs = jax.vmap(lambda kk: problem.grad_oracle(y, cid, kk))(ks)
            g = tm.tree_mean_leading(gs)
            return tm.tree_axpy(-eta, g, y), None

        keys = jax.random.split(key, self.local_steps)
        y, _ = jax.lax.scan(step, x0, keys)
        return y

    def round(self, problem, state, key):
        k_sample, k_local = jax.random.split(key)
        comm = state.comm
        if comm is not None:
            from repro.comm import config as comm_cfg

            comm_cfg.reject_algo_participation(self.s, self.name)
        s = (problem.num_clients if comm is not None
             else self.participation(problem))
        cids = base.sample_clients(k_sample, problem.num_clients, s)
        keys = jax.random.split(k_local, s)
        x_start = state.x
        if comm is not None:
            from repro import comm as comm_lib

            # clients local-step from the downlink reconstruction (bitwise
            # = state.x under an identity downlink leg) and the same point
            # is the delta wire reference
            x_start, comm = comm_lib.downlink(
                comm, state.x, comm_lib.downlink_key(key))
        with scope("local"):
            y_final = jax.vmap(
                lambda cid, kk: self._local(problem, x_start, cid, kk,
                                            state.eta))(cids, keys)
        if comm is not None:
            from repro.kernels.aggregate import ops as agg_ops

            if comm_cfg.ef_enabled(comm) and agg_ops.use_fused_aggregate():
                # fused EF round: the wire deltas ĉᵢ = C(y_i − x_start)
                # aggregate and apply in one kernel pass. The unfused
                # reconstruct-then-lerp is x + lr·(x_start + meanᵢwᵢĉᵢ − x);
                # meanᵢwᵢ = 1 by construction of the participation scale, so
                # it equals lerp(lr, x, x_start) − (−lr)·Σᵢ(wᵢ/S)·ĉᵢ to float
                # tolerance (x_start ≠ x under a lossy downlink; the lerp is
                # exactly x under an identity one)
                x, comm = comm_lib.uplink_fused_apply(
                    comm, y_final, cids, comm_lib.comm_key(key),
                    tm.tree_lerp(self.server_lr, state.x, x_start),
                    -self.server_lr, ref=x_start)
            else:
                y_hat, comm = comm_lib.uplink(
                    comm, y_final, cids, comm_lib.comm_key(key), ref=x_start)
                with scope("server"):
                    scale = comm_lib.participation_scale(comm.mask, cids)
                    y_mean = base.client_mean(state.x, y_hat,
                                              weight_scale=scale)
                    x = tm.tree_lerp(self.server_lr, state.x, y_mean)
            comm = comm_lib.account_round(
                comm, state.x, up_vectors=1, down_vectors=1)
        else:
            with scope("server"):
                y_mean = base.client_mean(state.x, y_final)
                x = tm.tree_lerp(self.server_lr, state.x, y_mean)
        return FedAvgState(x=x, eta=state.eta, r=state.r + 1, comm=comm)

    def init(self, problem, x0):
        return FedAvgState(x=x0, eta=jnp.asarray(self.eta), r=jnp.asarray(0))

    def output(self, state):
        return state.x
