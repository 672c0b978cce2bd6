"""Common machinery for federated algorithms (Algos 2–7 of the paper).

Conventions
-----------
* An algorithm is a small frozen dataclass of hyperparameters with

    init(problem, x0)            -> state   (a NamedTuple of pytrees)
    round(problem, state, key)   -> state   (ONE communication round, jittable)
    output(state)                -> params  (the returned iterate x̂)

* Uniform state protocol (relied on by the single-compile executors in
  ``core.runner``/``core.chain`` and the vmapped sweep engine in
  ``core.sweep``): every state is a NamedTuple carrying ``.x`` (the current
  server iterate), ``.eta`` (the base stepsize — kept in state so decay
  schedules can anneal it and sweeps can batch it) and ``.r`` (the round
  counter). ``round`` must pass ``eta`` through unchanged; the executor owns
  annealing. ``audit_state`` checks the protocol.
* Optional ``comm`` leaf: comm-aware algorithm states additionally carry
  ``comm: Optional[CommState] = None`` (``repro.comm``). ``None`` (the
  default) is an empty pytree — plain runs are untouched. The comm
  executors inject a ``CommState`` (with the round's participation mask)
  before each round; a comm-aware ``round`` compresses its uplinks through
  ``repro.comm.uplink``, aggregates with ``weight_scale`` masks, accounts
  bits via ``repro.comm.account_round`` and returns the updated leaf.
* Client sampling is uniform without replacement (paper §2).
* ``Grad`` (Algo 7): each sampled client averages K stochastic gradient
  queries at the server iterate.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import tree_math as tm
from repro.obs.profile import scope


REQUIRED_STATE_FIELDS = ("x", "eta", "r")


def audit_state(state):
    """Assert the uniform state protocol the executors and sweeps rely on."""
    missing = [f for f in REQUIRED_STATE_FIELDS if not hasattr(state, f)]
    if missing:
        raise TypeError(
            f"{type(state).__name__} violates the state protocol: missing "
            f"field(s) {missing}; executors need x/eta/r to schedule and "
            f"batch runs")
    if not hasattr(state, "_replace"):
        raise TypeError(f"{type(state).__name__} must be a NamedTuple")
    return state


def flat_params(x) -> bool:
    """True when params are a single flat [D] vector (the quadratic/theory
    problems) — the layout the fused Pallas aggregation kernels accept."""
    return isinstance(x, jax.Array) and x.ndim == 1


def weighted_client_mean(stacked, weight_scale):
    """meanᵢ(wᵢ·tᵢ) over the leading client axis, leaf-wise through the
    Pallas ``weighted_mean_over_clients`` kernel: each leaf [S, ...] is
    raveled to [S, d_leaf] rows at the kernel boundary
    (``tree_math.tree_ravel_rows``) and unraveled after — a flat [S, D]
    array is the single-leaf no-op-reshape case."""
    from repro.kernels.compress import ops as compress_ops

    means = jax.tree.map(
        lambda rows: compress_ops.weighted_mean_over_clients(
            rows, weight_scale),
        tm.tree_ravel_rows(stacked))
    return jax.tree.map(lambda m, t: m.reshape(t.shape[1:]), means, stacked)


@scope("server")
def client_mean(x, stacked, weight_scale=None):
    """Mean over the leading client axis of ``stacked``, routed through the
    Pallas ``mean_over_clients`` kernel when params are flat vectors (``x`` is
    the server iterate used only to pick the layout).

    ``weight_scale`` [S] (comm partial participation) switches to the masked
    aggregate meanᵢ(wᵢ·tᵢ) — leaf-wise on pytree params; callers pass
    ``m_i·S/Σm`` so masked-out clients drop out and the result is the
    participant mean. Under full participation every wᵢ is exactly 1.0,
    keeping the result bitwise equal to the plain mean."""
    from repro.kernels.aggregate import ops as agg_ops

    if weight_scale is not None:
        return weighted_client_mean(stacked, weight_scale)
    if flat_params(x):
        return agg_ops.mean_over_clients(stacked)
    return tm.tree_mean_leading(stacked)


@scope("server")
def fused_server_step(x, g_per, eta, *, c_i=None, c_mean=None,
                      weight_scale=None):
    """The (variance-reduced) server update x − η·(meanᵢ(gᵢ − cᵢ) + c̄).

    On flat [D] params this is one fused Pallas ``chain_aggregate`` pass —
    η is folded into the client weights (η/S each) and the server variate so
    the traced stepsize reaches the kernel as data while ``lr`` stays static.
    ``c_i``/``c_mean`` default to zero (plain gradient averaging, Algo 2).
    ``weight_scale`` [S] rescales per-client weights (comm participation
    masks, exactly 1.0 per client under full participation); on pytree
    params the masked mean runs leaf-wise through the weighted-aggregate
    kernel (``weighted_client_mean``).
    """
    from repro.kernels.aggregate import ops as agg_ops

    if flat_params(x):
        s = g_per.shape[0]
        base_w = (jnp.full((s,), 1.0, jnp.float32) if weight_scale is None
                  else weight_scale.astype(jnp.float32))
        w = base_w * (eta / s)
        ci = jnp.zeros_like(g_per) if c_i is None else c_i
        c = jnp.zeros_like(x) if c_mean is None else eta * c_mean
        return agg_ops.chain_aggregate(x, g_per, ci, c, weights=w, lr=1.0)
    if weight_scale is not None:
        diff = (g_per if c_i is None
                else jax.tree.map(jnp.subtract, g_per, c_i))
        g = weighted_client_mean(diff, weight_scale)
        if c_mean is not None:
            g = tm.tree_add(g, c_mean)
        return tm.tree_axpy(-eta, g, x)
    if c_i is None:
        g = tm.tree_mean_leading(g_per)
    else:
        g = jax.tree.map(lambda gp, ci, cm: jnp.mean(gp - ci, axis=0) + cm,
                         g_per, c_i, c_mean)
    return tm.tree_axpy(-eta, g, x)


@scope("sample")
def sample_clients(key, num_clients: int, s: int):
    """S of N uniformly without replacement (paper §2).

    Implemented as an integer-only Fisher–Yates partial shuffle rather than
    ``jax.random.choice(replace=False)`` (or any argsort-of-randoms): the
    sort-based samplers fuse with the downstream client-data gathers, and
    XLA's single-device and multi-device (SPMD) pipelines lower that fusion
    DIFFERENTLY — the sampled permutation itself then changes between the
    vmapped and device-sharded sweep engines. Integer swaps admit no such
    rewrite, so the draw is bitwise identical under every pipeline, which
    the sharded grid engine (``repro.dist``) relies on for bit-exact
    equality with the single-device path.
    """
    if not 0 < s <= num_clients:
        # jax.random.choice(replace=False) used to reject this at trace
        # time; the partial shuffle below would silently clamp instead
        raise ValueError(
            f"cannot sample {s} of {num_clients} clients without "
            f"replacement")
    idx = jnp.arange(num_clients, dtype=jnp.int32)
    keys = jax.random.split(key, s)

    def swap(i, idx):
        j = jax.random.randint(keys[i], (), i, num_clients, dtype=jnp.int32)
        vi = idx[i]
        vj = idx[j]
        return idx.at[i].set(vj).at[j].set(vi)

    idx = jax.lax.fori_loop(0, s, swap, idx)
    return idx[:s]


def participant_block(num_clients: int) -> int:
    """B, the client rows one block of ``participant_rows`` computes: a
    static function of N alone, ⌈N/8⌉ (13 at N = 100)."""
    return -(-num_clients // 8)


def local_rows(num_clients: int, cohort: int) -> int:
    """Rows ``participant_rows`` computes a round for ``cohort``
    participants of ``num_clients``: whole blocks of B."""
    b = participant_block(num_clients)
    return b * -(-cohort // b)


def participant_rows(fn, cids, keys, mask, fill):
    """``fn(cids, keys)`` for the round's participants only, in blocks.

    ``fn`` maps [B] client ids and their [B, ...] keys to a pytree of [B, ...]
    rows. ``cids`` [N] is the round's permutation, ``keys`` its per-position
    keys and ``mask`` [N] the participation mask by client id. Positions
    whose client has a nonzero mask come first (a stable sort, so in their
    permutation order) and are computed ⌈P/B⌉ blocks at a time, P read from
    the mask at run time, so participation never changes a shape. Each
    position keeps its key, so every participant's row is what computing
    all N rows would give it; the other rows hold ``fill`` (one row, a
    pytree like ``fn``'s rows) unless a block's tail reaches them, and carry
    no signal: the comm layer gives them weight 0 and keeps their residual.
    Returns the [N, ...] rows in permutation order.
    """
    n = cids.shape[0]
    b = participant_block(n)
    part = mask[cids] != 0
    order = jnp.argsort((~part).astype(jnp.int32), stable=True)
    n_blocks = (jnp.sum(part, dtype=jnp.int32) + (b - 1)) // b
    rows0 = jax.tree.map(lambda l: jnp.broadcast_to(l, (n,) + l.shape), fill)

    def body(carry):
        i, rows = carry
        # the last block may be clamped back into range: its first rows are
        # then computed again, to the same values
        pos = jax.lax.dynamic_slice(order, (i * b,), (b,))
        out = fn(cids[pos], keys[pos])
        return i + 1, jax.tree.map(lambda t, v: t.at[pos].set(v), rows, out)

    _, rows = jax.lax.while_loop(lambda c: c[0] < n_blocks, body,
                                 (jnp.int32(0), rows0))
    return rows


@scope("local")
def grad_k(problem, x, client_ids, key, k: int, *, keys=None):
    """Algo 7 ``Grad``: per-client average of K stochastic gradients at x.

    Returns a pytree whose leaves have a leading [S] axis. ``keys``
    optionally supplies the [S, k, 2] per-query key rows directly (the
    derivation below, precomputed) — the client-sharded round
    (``repro.dist.client_axis``) passes each shard its rows so the oracle
    streams match the single-device round exactly.
    """
    s = client_ids.shape[0]
    if keys is None:
        keys = jax.random.split(key, s * k).reshape(s, k, -1)

    def per_client(cid, ks):
        gs = jax.vmap(lambda kk: problem.grad_oracle(x, cid, kk))(ks)
        return tm.tree_mean_leading(gs)

    return jax.vmap(per_client)(client_ids, keys)


def value_k(problem, x, client_ids, key, k: int):
    """Average of K stochastic function-value queries per client, then mean."""
    s = client_ids.shape[0]
    keys = jax.random.split(key, s * k).reshape(s, k, -1)

    def per_client(cid, ks):
        vs = jax.vmap(lambda kk: problem.value_oracle(x, cid, kk))(ks)
        return jnp.mean(vs)

    return jnp.mean(jax.vmap(per_client)(client_ids, keys))


class AvgTracker(NamedTuple):
    """Numerically-stable tracker for x̂ = (1/W_R)·Σ w_r x_r, w_r=(1−ημ)^{−r}.

    Normalized recurrence: W'_r = 1 + (1−ημ)·W'_{r−1};
    avg_r = avg_{r−1} + (x_r − avg_{r−1}) / W'_r.
    """

    avg: object
    wprime: jnp.ndarray

    @staticmethod
    def init(x):
        return AvgTracker(avg=x, wprime=jnp.asarray(1.0))

    def update(self, x, decay: jnp.ndarray):
        """decay = (1 − ημ) ∈ (0, 1]; decay=1 gives the uniform average."""
        wprime = 1.0 + decay * self.wprime
        avg = jax.tree.map(lambda a, b: a + (b - a) / wprime, self.avg, x)
        return AvgTracker(avg=avg, wprime=wprime)


@dataclasses.dataclass(frozen=True)
class FederatedAlgorithm:
    """Base class; concrete algorithms override init/round/output."""

    eta: float = 0.1
    k: int = 16  # oracle queries per client per round (paper's K)
    s: int = 0  # sampled clients per round; 0 => full participation (S=N)
    name: str = "base"

    def participation(self, problem):
        return self.s if self.s and self.s > 0 else problem.num_clients

    def init_with_eta(self, problem, x0, eta=None):
        """``init`` with an optional stepsize override written into state —
        the hook the sweep engine batches over."""
        state = self.init(problem, x0)
        if eta is not None:
            state = state._replace(
                eta=jnp.asarray(eta, jnp.result_type(state.eta)))
        return state

    # --- to be overridden -------------------------------------------------
    def init(self, problem, x0):
        raise NotImplementedError

    def round(self, problem, state, key):
        raise NotImplementedError

    def output(self, state):
        return state.x
