"""SSNM (Zhou et al. 2019) — the paper's Algorithm 6: Nesterov-accelerated
SAGA via sampled negative momentum.

The client losses are viewed as F_i(x) = F̃_i(x) + h(x), with h(x) = μ_h/2·||x||²
the strongly-convex part (paper App. D.4: the usual strong-convexity assumption
converts to this form). The oracle returns ∇F_i, so ∇F̃_i(y) = ∇F_i(y) − μ_h·y.

Round r (with per-client snapshots φ_i and control variates c_i = ∇F̃_i(φ_i)):
  sample S:        y_i = τ·x + (1−τ)·φ_i,  i ∈ S
  g = mean_i(∇F̃_i(y_i) − c_i) + c̄
  x⁺ = argmin_x h(x) + ⟨g, x⟩ + 1/(2η)||x − x_r||²  =  (x_r − η·g)/(1 + η·μ_h)
  fresh sample S′: φ_I ← τ·x⁺ + (1−τ)·φ_I,  c_I ← ∇F̃_I(φ_I⁺)

Parameter choices follow Thm. D.5's two cases on (N/S)/κ.

Comm-aware: compressed variance reduction in the style of Zhao et al.
("Faster Rates for Compressed Federated Learning with Client-Variance
Reduction") — the iterate broadcasts through the downlink-EF chain and the
new snapshot point x⁺ through the stateless second downlink; both gradient
uplinks (the sampled-negative-momentum gradients and the fresh snapshot
gradients) ride the MOMENTUM leg, the first through the error-feedback
path, the second without EF (SAGA Option II's convention — the residual
stream belongs to the step gradients). The control-variate table stores the
TRANSMITTED values, and masked-out clients keep their snapshots.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import tree_math as tm
from repro.core.algorithms import base
from repro.obs.profile import scope


class SSNMState(NamedTuple):
    x: object
    phi_table: object  # [N, ...] snapshots
    c_table: object  # [N, ...] ∇F̃_i(φ_i)
    c_mean: object
    eta: jnp.ndarray
    r: jnp.ndarray
    comm: Optional[object] = None


@dataclasses.dataclass(frozen=True)
class SSNM(base.FederatedAlgorithm):
    mu_h: float = 0.1  # strong convexity of h
    beta: float = 1.0
    tau: float = -1.0  # <0 => derive via Thm. D.5
    name: str = "ssnm"

    def hyper(self, problem):
        """Thm. D.5 stepsize/momentum: two cases on (N/S)/κ."""
        n = problem.num_clients
        s = self.participation(problem)
        kappa = self.beta / self.mu_h
        ratio = (n / s) / kappa
        if ratio > 0.75:
            eta = 1.0 / (2.0 * self.mu_h * (n / s))
        else:
            eta = (1.0 / (3.0 * self.mu_h * (n / s) * self.beta)) ** 0.5
        tau = self.tau if self.tau >= 0 else ((n / s) * eta * self.mu_h) / (1.0 + eta * self.mu_h)
        return eta, tau

    @scope("local")
    def _tilde_grad_k(self, problem, y, cid, key):
        ks = jax.random.split(key, self.k)
        gs = jax.vmap(lambda kk: problem.grad_oracle(y, cid, kk))(ks)
        g = tm.tree_mean_leading(gs)
        return jax.tree.map(lambda gg, yy: gg - self.mu_h * yy, g, y)

    def init(self, problem, x0):
        n = problem.num_clients
        eta, _ = self.hyper(problem)
        phi = tm.tree_broadcast_leading(x0, n)

        def c0(i):
            g = jax.grad(problem.client_loss)(x0, i)
            return jax.tree.map(lambda gg, yy: gg - self.mu_h * yy, g, x0)

        c_table = jax.vmap(c0)(jnp.arange(n))
        return SSNMState(
            x=x0, phi_table=phi, c_table=c_table,
            c_mean=tm.tree_mean_leading(c_table),
            eta=jnp.asarray(eta), r=jnp.asarray(0),
        )

    def round(self, problem, state, key):
        k_s1, k_g1, k_s2, k_g2 = jax.random.split(key, 4)
        comm = state.comm
        n = problem.num_clients
        if comm is not None:
            from repro import comm as comm_lib
            from repro.comm import config as comm_cfg

            comm_cfg.reject_algo_participation(self.s, self.name)
            s = n  # all N compute (static shape); the mask decides who ships
        else:
            s = self.participation(problem)
        eta, tau = self.hyper(problem)
        eta = state.eta  # annealable

        cids = base.sample_clients(k_s1, n, s)
        phi_i = jax.tree.map(lambda t: t[cids], state.phi_table)
        c_i = jax.tree.map(lambda t: t[cids], state.c_table)
        x_b = state.x
        if comm is not None:
            # the iterate broadcasts through the downlink-EF chain; clients
            # form y_i at the reconstruction
            x_b, comm = comm_lib.downlink(
                comm, state.x, comm_lib.downlink_key(key))
        y_i = jax.tree.map(lambda p, xx: tau * xx[None] + (1 - tau) * p, phi_i,
                           jax.tree.map(lambda l: l, x_b))
        keys = jax.random.split(k_g1, s)
        g_per = jax.vmap(lambda cid, y, kk: self._tilde_grad_k(problem, y, cid, kk))(
            cids, y_i, keys
        )
        if comm is not None:
            # sampled-negative-momentum gradients ride the MOMENTUM leg
            # through the compressed + error-feedback path
            g_per, comm = comm_lib.uplink(
                comm, g_per, cids, comm_lib.momentum_uplink_key(key),
                leg="mom")
            scale = comm_lib.participation_scale(comm.mask, cids)
            x_lin = base.fused_server_step(state.x, g_per, eta,
                                           c_i=c_i, c_mean=state.c_mean,
                                           weight_scale=scale)
        else:
            # fused x − η·(mean(g−c_i) + c̄), then closed-form prox scaling
            x_lin = base.fused_server_step(state.x, g_per, eta,
                                           c_i=c_i, c_mean=state.c_mean)
        x_new = jax.tree.map(lambda t: t / (1.0 + eta * self.mu_h), x_lin)

        # fresh sample S' for snapshot/control updates
        cids2 = base.sample_clients(k_s2, n, s)
        phi_old2 = jax.tree.map(lambda t: t[cids2], state.phi_table)
        x2 = x_new
        if comm is not None:
            # the snapshot point is the round's second broadcast (stateless
            # downlink — the down_ref chain tracks the iterate broadcasts)
            x2 = comm_lib.downlink_second(
                comm, x_new, comm_lib.second_downlink_key(key))
        phi_new2 = jax.tree.map(lambda p, xx: tau * xx[None] + (1 - tau) * p, phi_old2,
                                jax.tree.map(lambda l: l, x2))
        keys2 = jax.random.split(k_g2, s)
        c_new2 = jax.vmap(lambda cid, p, kk: self._tilde_grad_k(problem, p, cid, kk))(
            cids2, phi_new2, keys2
        )
        c_old2 = jax.tree.map(lambda t: t[cids2], state.c_table)
        if comm is not None:
            # fresh snapshot gradients: second momentum-leg uplink, no EF
            # (SAGA Option II's convention); server tables keep TRANSMITTED
            # values, masked-out clients keep their snapshots
            c_new2, comm = comm_lib.uplink(
                comm, c_new2, cids2, comm_lib.second_uplink_key(key),
                use_ef=False, leg="mom")
            m2 = comm.mask[cids2]
            phi_new2 = comm_cfg.masked_keep(m2, phi_new2, phi_old2)
            c_new2 = comm_cfg.masked_keep(m2, c_new2, c_old2)
        phi_table = tm.tree_scatter_set(state.phi_table, cids2, phi_new2)
        c_table = tm.tree_scatter_set(state.c_table, cids2, c_new2)
        delta = tm.tree_mean_leading(jax.tree.map(jnp.subtract, c_new2, c_old2))
        c_mean = tm.tree_axpy(s / n, delta, state.c_mean)
        if comm is not None:
            comm = comm_lib.account_round(
                comm, state.x, mom_vectors=2, down_vectors=2)

        return SSNMState(
            x=x_new, phi_table=phi_table, c_table=c_table, c_mean=c_mean,
            eta=state.eta, r=state.r + 1, comm=comm,
        )

    def output(self, state):
        return state.x
