"""Selection policies: per-round participation masks from a traced switch.

``round_select`` is the in-executor entry point.  It consumes one raw
per-round selection key (the ``sel_keys`` scan operand, derived host-side
from the policy's ``sel_seed`` — a stream SEPARATE from the algorithm's
round keys, so adding a policy never perturbs algorithm randomness), probes
the clients when the policy calls for it, and dispatches on
``params.policy_id`` through ``jax.lax.switch``:

* ``uniform`` (0) — draws ``uniform(sel_key, (N,))`` and keeps the S
  smallest by double-argsort rank, the EXACT construction of
  ``CommConfig.round_masks``; with matching seed/fold derivation the
  trajectory is bitwise identical to the precomputed mask-schedule path.
  Never probes, bills zero probe bits.
* ``power_of_choice`` (1) — probes every client's stochastic loss value at
  the current iterate and keeps the top-S by loss (Cho et al.'s
  power-of-choice, with the candidate set widened to all N).
* ``ucb`` (2) — a UCB bandit over per-client loss reductions: the reward
  observed for last round's participants is ``last_probe - probe`` (how much
  their own loss fell over the round they served in), folded into a
  running mean; the score is mean + ``ucb_c``·sqrt(log(t+1)/counts), with
  never-selected clients forced to +inf (stable argsort then yields an
  index-order round-robin warm start).
* ``shapley`` (3) — greedy selection on GTG-style marginal-contribution
  estimates: the round's global loss drop is allocated over last round's
  participants efficiency-preservingly (equal split of the global gain plus
  each participant's centered own-loss deviation), EMA'd into a per-client
  contribution table, top-S by contribution.

Every branch performs the same bookkeeping (counts += mask, last_mask =
mask, t += 1) so state invariants hold policy-independently:
``counts.sum() == S·R`` and ``t == R`` after R rounds.

Key-stream discipline: the uniform branch consumes the raw per-round
``sel_key`` verbatim (bitwise parity with ``CommConfig.round_masks``
requires it); probing branches derive their oracle keys from
``fold_in(sel_key, _PROBE_KEY_TAG)`` so the two streams never collide.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.obs.profile import scope
from repro.selection.state import (POLICY_IDS, PROBING_POLICIES, PolicyParams,
                                   PolicyState, init_state, make_params)

#: domain-separation tag for the probe key stream ('sl')
_PROBE_KEY_TAG = 0x736C


def _smallest_s_mask(v, s):
    """Mask keeping the ``s`` smallest entries of ``v`` (float32 0/1).

    Double-argsort ranks, the same construction as
    ``CommConfig.round_masks`` — jnp.argsort is stable, so ties break in
    index order deterministically across engines.
    """
    ranks = jnp.argsort(jnp.argsort(v))
    return (ranks < s).astype(jnp.float32)


def top_s_mask(score, s):
    """Mask keeping the ``s`` LARGEST scores (ties → lowest index first)."""
    return _smallest_s_mask(-score, s)


def probe_values(problem, x, key):
    """Stochastic loss value of every client at ``x`` — one oracle call per
    client on an independent subkey. [N] float32."""
    n = problem.num_clients
    keys = jax.random.split(key, n)
    cids = jnp.arange(n, dtype=jnp.int32)
    return jax.vmap(lambda i, kk: problem.value_oracle(x, i, kk))(cids, keys)


def probe_bits(params: PolicyParams, num_clients: int):
    """Uplink bits billed for the value probe: one float32 scalar from each
    of the N clients for probing policies, zero for uniform.  The probe
    evaluates at the model clients already hold from the round's broadcast,
    so no extra model downlink is charged (the standard power-of-choice
    accounting convention)."""
    return jnp.where(params.policy_id == POLICY_IDS["uniform"],
                     jnp.float32(0.0), jnp.float32(32.0 * num_clients))


@scope("sample")
def round_select(problem, x, pstate: PolicyState, params: PolicyParams, key):
    """One selection step: ``(mask [N] float32, new PolicyState)``.

    ``key`` is the round's raw selection key (row of the ``sel_keys``
    operand).  Dispatch is a ``lax.switch`` over ``params.policy_id`` —
    all branches share one output structure, so the policy choice is pure
    data and never re-traces the executor.
    """
    n = problem.num_clients
    s = params.s_sel

    v = probe_values(problem, x, jax.random.fold_in(key, _PROBE_KEY_TAG))

    def bookkeep(mask, probe, values, contrib):
        return PolicyState(
            counts=pstate.counts + mask, values=values, contrib=contrib,
            last_probe=probe, last_mask=mask, t=pstate.t + 1.0)

    def _uniform(_v):
        # raw key, double-argsort rank: bitwise CommConfig.round_masks
        u = jax.random.uniform(key, (n,))
        mask = _smallest_s_mask(u, s)
        return mask, bookkeep(mask, pstate.last_probe, pstate.values,
                              pstate.contrib)

    def _power_of_choice(v):
        mask = top_s_mask(v, s)
        return mask, bookkeep(mask, v, pstate.values, pstate.contrib)

    def _ucb(v):
        served = pstate.last_mask
        reward = pstate.last_probe - v
        cnt = jnp.maximum(pstate.counts, 1.0)
        values = jnp.where(served > 0,
                           pstate.values + (reward - pstate.values) / cnt,
                           pstate.values)
        t = pstate.t + 1.0
        bonus = params.ucb_c * jnp.sqrt(jnp.log(t + 1.0) / cnt)
        score = jnp.where(pstate.counts < 0.5, jnp.inf, values + bonus)
        mask = top_s_mask(score, s)
        return mask, bookkeep(mask, v, values, pstate.contrib)

    def _shapley(v):
        served = pstate.last_mask
        s_prev = jnp.maximum(jnp.sum(served), 1.0)
        gain = jnp.mean(pstate.last_probe) - jnp.mean(v)
        own = (pstate.last_probe - v) * served
        own_mean = jnp.sum(own) / s_prev
        marginal = (gain / s_prev + (own - own_mean)) * served
        contrib = jnp.where(served > 0,
                            (1.0 - params.ema) * pstate.contrib
                            + params.ema * marginal,
                            pstate.contrib)
        score = jnp.where(pstate.counts < 0.5, jnp.inf, contrib)
        mask = top_s_mask(score, s)
        return mask, bookkeep(mask, v, pstate.values, contrib)

    return jax.lax.switch(params.policy_id,
                          [_uniform, _power_of_choice, _ucb, _shapley], v)


@dataclasses.dataclass(frozen=True)
class SelectionPolicy:
    """Host-side policy description; everything traced goes through
    ``params()``/``init_state()``/``sel_keys()`` as operands."""

    policy: str = "uniform"
    participation: float = 1.0
    ucb_c: float = 1.0
    ema: float = 0.5
    sel_seed: int = 0

    def __post_init__(self):
        if self.policy not in POLICY_IDS:
            raise ValueError(
                f"unknown selection policy {self.policy!r}; "
                f"known: {sorted(POLICY_IDS)}")
        if not 0.0 < self.participation <= 1.0:
            raise ValueError("participation must be in (0, 1]")
        if self.ucb_c < 0:
            raise ValueError("ucb_c must be >= 0")
        if not 0.0 < self.ema <= 1.0:
            raise ValueError("ema must be in (0, 1]")

    @property
    def name(self) -> str:
        tag = self.policy
        if self.participation < 1.0:
            tag += f"@{self.participation:g}"
        return tag

    @property
    def probing(self) -> bool:
        return self.policy in PROBING_POLICIES

    def clients_per_round(self, num_clients: int) -> int:
        return max(1, round(self.participation * num_clients))

    def params(self, num_clients: int) -> PolicyParams:
        return make_params(self.policy, self.clients_per_round(num_clients),
                           ucb_c=self.ucb_c, ema=self.ema)

    def init_state(self, num_clients: int) -> PolicyState:
        return init_state(num_clients)

    def sel_keys(self, rounds: int, fold: int = 0):
        """[rounds, 2] raw per-round selection keys — the scan operand.

        Derivation is EXACTLY ``CommConfig.round_masks``'s (fold_in the
        per-cell fold into PRNGKey(seed), split into rounds) — that is what
        makes the uniform policy bitwise-reproduce the precomputed
        mask-schedule path at ``sel_seed == mask_seed``.  It is also
        policy-INDEPENDENT: every policy at the same (seed, fold) consumes
        the same randomness, so policy comparisons are paired."""
        key = jax.random.fold_in(jax.random.PRNGKey(self.sel_seed), fold)
        return jax.random.split(key, rounds)

    def round_masks(self, rounds: int, num_clients: int, fold: int = 0):
        """Host-side replay of the uniform policy's masks (for parity
        checks against the precomputed mask-schedule path).  Adaptive
        policies depend on in-run probe values and cannot be replayed."""
        if self.policy != "uniform":
            raise ValueError(
                f"round_masks is only defined for the uniform policy "
                f"(got {self.policy!r}: adaptive masks depend on the run)")
        s = self.clients_per_round(num_clients)
        keys = self.sel_keys(rounds, fold)

        def one_round(k):
            u = jax.random.uniform(k, (num_clients,))
            return _smallest_s_mask(u, s)

        return jax.vmap(one_round)(keys)
