"""SCAFFOLD (Karimireddy et al. 2020b) — local-update baseline with client
control variates. Used by the paper both as a baseline and as A_local in the
SCAFFOLD→SGD chain (§6).

Per sampled client i:
  y ← y − η·(g_i(y) − c_i + c)        (local_steps times)
  c_i⁺ = c_i − c + (x − y_final)/(local_steps·η)      (Option II of the paper)
Server:
  x ← x + server_lr · mean_i (y_i − x)
  c ← c + (S/N) · mean_i (c_i⁺ − c_i)

Comm-aware: clients uplink TWO compressed vectors per round — the iterate
delta (y_i − x) and the control-variate delta (c_i⁺ − c_i); the server
broadcasts two (x and c). Masked-out clients keep their table entries.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import tree_math as tm
from repro.core.algorithms import base
from repro.obs.profile import scope


class ScaffoldState(NamedTuple):
    x: object
    c_table: object  # [N, ...] per-client control variates
    c: object  # server control variate
    eta: jnp.ndarray
    r: jnp.ndarray
    comm: Optional[object] = None


@dataclasses.dataclass(frozen=True)
class Scaffold(base.FederatedAlgorithm):
    local_steps: int = 4
    inner_batch: int = 4
    server_lr: float = 1.0
    name: str = "scaffold"

    def init(self, problem, x0):
        n = problem.num_clients
        return ScaffoldState(
            x=x0,
            c_table=tm.tree_broadcast_leading(tm.tree_zeros_like(x0), n),
            c=tm.tree_zeros_like(x0),
            eta=jnp.asarray(self.eta),
            r=jnp.asarray(0),
        )

    def round(self, problem, state, key):
        k_sample, k_local = jax.random.split(key)
        comm = state.comm
        if comm is not None:
            from repro.comm import config as comm_cfg

            comm_cfg.reject_algo_participation(self.s, self.name)
        n = problem.num_clients
        s = n if comm is not None else self.participation(problem)
        cids = base.sample_clients(k_sample, problem.num_clients, s)
        keys = jax.random.split(k_local, s)
        c_i = jax.tree.map(lambda t: t[cids], state.c_table)
        x_start, c_start = state.x, state.c
        if comm is not None:
            from repro import comm as comm_lib

            # both broadcasts ride the downlink leg: the iterate through
            # the bidirectional-EF chain, the server variate stateless —
            # bitwise pass-throughs under an identity downlink leg
            x_start, comm = comm_lib.downlink(
                comm, state.x, comm_lib.downlink_key(key))
            c_start = comm_lib.downlink_second(
                comm, state.c, comm_lib.second_downlink_key(key))

        def local(cid, ci, kk):
            def step(y, k_step):
                ks = jax.random.split(k_step, self.inner_batch)
                gs = jax.vmap(lambda k2: problem.grad_oracle(y, cid, k2))(ks)
                g = tm.tree_mean_leading(gs)
                corr = jax.tree.map(lambda gg, cc, sc: gg - cc + sc, g, ci, c_start)
                return tm.tree_axpy(-state.eta, corr, y), None

            y, _ = jax.lax.scan(step, x_start, jax.random.split(kk, self.local_steps))
            ci_new = jax.tree.map(
                lambda cc, sc, x0_, yf: cc - sc + (x0_ - yf) / (self.local_steps * state.eta),
                ci, c_start, x_start, y,
            )
            return y, ci_new

        with scope("local"):
            y_final, ci_new = jax.vmap(local)(cids, c_i, keys)
        if comm is not None:
            k_comm = comm_lib.comm_key(key)
            y_final, comm = comm_lib.uplink(
                comm, y_final, cids, k_comm, ref=x_start)
            # control deltas ride a second uplink (per-row reference, no EF)
            ci_new, comm = comm_lib.uplink(
                comm, ci_new, cids, comm_lib.second_uplink_key(key),
                ref=c_i, use_ef=False)
            from repro.comm import config as comm_cfg

            m = comm.mask[cids]
            scale = comm_lib.participation_scale(comm.mask, cids)
            y_mean = base.client_mean(state.x, y_final, weight_scale=scale)
            ci_new = comm_cfg.masked_keep(m, ci_new, c_i)
            comm = comm_lib.account_round(
                comm, state.x, up_vectors=2, down_vectors=2)
        else:
            y_mean = base.client_mean(state.x, y_final)
        x = tm.tree_lerp(self.server_lr, state.x, y_mean)
        delta_c = tm.tree_mean_leading(jax.tree.map(jnp.subtract, ci_new, c_i))
        c = tm.tree_axpy(s / n, delta_c, state.c)
        c_table = tm.tree_scatter_set(state.c_table, cids, ci_new)
        return ScaffoldState(x=x, c_table=c_table, c=c, eta=state.eta,
                             r=state.r + 1, comm=comm)

    def output(self, state):
        return state.x


@dataclasses.dataclass(frozen=True)
class FedProx(base.FederatedAlgorithm):
    """FedProx (Li et al. 2018): FedAvg with a proximal term μ_prox/2·||y−x||²
    added to every local objective. Baseline local-update method."""

    local_steps: int = 4
    inner_batch: int = 4
    server_lr: float = 1.0
    prox_mu: float = 0.1
    name: str = "fedprox"

    def init(self, problem, x0):
        from repro.core.algorithms.fedavg import FedAvgState

        return FedAvgState(x=x0, eta=jnp.asarray(self.eta), r=jnp.asarray(0))

    def round(self, problem, state, key):
        from repro.core.algorithms.fedavg import FedAvgState

        k_sample, k_local = jax.random.split(key)
        s = self.participation(problem)
        cids = base.sample_clients(k_sample, problem.num_clients, s)
        keys = jax.random.split(k_local, s)

        def local(cid, kk):
            def step(y, k_step):
                ks = jax.random.split(k_step, self.inner_batch)
                gs = jax.vmap(lambda k2: problem.grad_oracle(y, cid, k2))(ks)
                g = tm.tree_mean_leading(gs)
                g = jax.tree.map(
                    lambda gg, yy, xx: gg + self.prox_mu * (yy - xx), g, y, state.x
                )
                return tm.tree_axpy(-state.eta, g, y), None

            y, _ = jax.lax.scan(step, state.x, jax.random.split(kk, self.local_steps))
            return y

        with scope("local"):
            y_final = jax.vmap(local)(cids, keys)
        y_mean = base.client_mean(state.x, y_final)
        x = tm.tree_lerp(self.server_lr, state.x, y_mean)
        return FedAvgState(x=x, eta=state.eta, r=state.r + 1)

    def output(self, state):
        return state.x
