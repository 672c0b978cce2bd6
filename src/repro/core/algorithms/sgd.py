"""SGD — the paper's Algorithm 2 (global-update method).

Each round: sample S clients, every sampled client returns the average of K
stochastic gradients at the server iterate (Algo 7), the server averages and
takes one step. The returned iterate follows Thm. D.1:

  * strongly convex: weighted average with w_r = (1 − ημ)^{−(r+1)}
  * general convex:  uniform average
  * PL:              last iterate

On flat [D] parameter vectors (the quadratic/theory problems) the server step
runs through the fused Pallas aggregation kernel (``kernels.aggregate.ops``):
η is folded into the client weights (η/S each) so the traced stepsize reaches
the kernel as data while ``lr`` stays static.

Comm-aware: with a ``comm`` leaf injected (``repro.comm``), each of the
round's participants computes its K-sample gradient
(``base.participant_rows``), the uplink is compressed (g is the wire
payload), and the server step averages over the round's participation mask.
With the identity compressor and full participation this path is bit-exact
with the plain one.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax.numpy as jnp

from repro.core import tree_math as tm
from repro.core.algorithms import base
from repro.obs.profile import scope


class SGDState(NamedTuple):
    x: object
    tracker: base.AvgTracker
    eta: jnp.ndarray
    r: jnp.ndarray
    comm: Optional[object] = None


@dataclasses.dataclass(frozen=True)
class SGD(base.FederatedAlgorithm):
    mu_avg: float = 0.0  # μ used for the Thm. D.1 averaging weights
    output_mode: str = "weighted_avg"  # weighted_avg | uniform_avg | last
    name: str = "sgd"

    def init(self, problem, x0):
        return SGDState(
            x=x0,
            tracker=base.AvgTracker.init(x0),
            eta=jnp.asarray(self.eta),
            r=jnp.asarray(0),
        )

    def round(self, problem, state, key):
        import jax

        k_sample, k_grad = jax.random.split(key)
        comm = state.comm
        if comm is not None:
            from repro import comm as comm_lib
            from repro.comm import config as comm_cfg
            from repro.kernels.aggregate import ops as agg_ops

            # the round's mask decides who computes and transmits — an
            # algorithm-level s would be silently ignored
            comm_cfg.reject_algo_participation(self.s, self.name)
            n = problem.num_clients
            cids = base.sample_clients(k_sample, n, n)
            # broadcast the iterate through the downlink leg: clients
            # compute at the reconstruction (bitwise = state.x under an
            # identity downlink); the server step stays at the exact iterate
            x_b, comm = comm_lib.downlink(
                comm, state.x, comm_lib.downlink_key(key))
            # participants only, each with the key of its position; the
            # others send a zero gradient
            keys = jax.random.split(k_grad, n * self.k).reshape(n, self.k, -1)
            with scope("local"):
                g_per = base.participant_rows(
                    lambda c, ks: base.grad_k(problem, x_b, c, None, self.k,
                                              keys=ks),
                    cids, keys, comm.mask, tm.tree_zeros_like(x_b))
            if comm_cfg.ef_enabled(comm) and agg_ops.use_fused_aggregate():
                # one fused kernel pass: masked weighted mean + EF residual
                # update + server step — bitwise identical to the unfused
                # sequence below on kernel backends (same einsum order,
                # η folded into the weights the same way)
                x, comm = comm_lib.uplink_fused_apply(
                    comm, g_per, cids, comm_lib.comm_key(key), state.x,
                    state.eta)
            else:
                g_hat, comm = comm_lib.uplink(
                    comm, g_per, cids, comm_lib.comm_key(key))
                scale = comm_lib.participation_scale(comm.mask, cids)
                x = base.fused_server_step(state.x, g_hat, state.eta,
                                           weight_scale=scale)
            comm = comm_lib.account_round(
                comm, state.x, up_vectors=1, down_vectors=1)
        else:
            s = self.participation(problem)
            cids = base.sample_clients(k_sample, problem.num_clients, s)
            g_per = base.grad_k(problem, state.x, cids, k_grad, self.k)
            x = base.fused_server_step(state.x, g_per, state.eta)
        with scope("server"):
            decay = jnp.asarray(1.0 - state.eta * self.mu_avg)
            tracker = state.tracker.update(x, jnp.clip(decay, 0.0, 1.0))
        return SGDState(x=x, tracker=tracker, eta=state.eta, r=state.r + 1,
                        comm=comm)

    def output(self, state):
        if self.output_mode == "last":
            return state.x
        return state.tracker.avg
