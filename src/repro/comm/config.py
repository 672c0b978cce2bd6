"""Comm configuration, per-run state, and the comm operators for BOTH wire
directions.

``CommPlan`` is the user-facing static description: one ``Leg`` per wire
direction (uplink, downlink, and the momentum uplink ASG/SSNM ship their
accelerated gradients on). Everything a plan produces for the executors —
``CommParams`` scalars per leg, the per-round participation mask schedule,
the ``CommState`` carried in algorithm state — is runtime data, so swapping
any compressor on any leg at fixed shapes re-uses the compiled executor.
``CommConfig`` survives as a deprecation shim constructing an uplink-only
plan, bitwise identical to the pre-plan behaviour. See the package docstring
for the bits model.
"""
from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.comm import compressors
from repro.comm.compressors import COMP_IDENTITY, COMP_QSGD, CommParams
from repro.core import tree_math as tm
from repro.obs.profile import scope

# fold_in tag deriving the comm PRNG stream from a round key WITHOUT
# disturbing the key splits the algorithms already perform (bit-exactness of
# identity-compressor runs depends on this).
_COMM_KEY_TAG = 0x636D
# second-uplink stream tag (see second_uplink_key); registered in
# repro.analysis.REGISTERED_KEY_TAGS
_SECOND_UPLINK_TAG = 1
# downlink-EF broadcast stream tag (see downlink_key); registered in
# repro.analysis.REGISTERED_KEY_TAGS
_DOWNLINK_KEY_TAG = 2
# compressed-momentum uplink stream tag (see momentum_uplink_key);
# registered in repro.analysis.REGISTERED_KEY_TAGS
_MOMENTUM_UPLINK_TAG = 3


class CommState(NamedTuple):
    """The optional ``comm`` leaf of the uniform state protocol.

    All fields are arrays or pytrees of arrays (operand data). ``mask`` is
    the CURRENT round's participation mask — the executor overwrites it each
    scan step from the precomputed schedule. ``residual`` is the per-client
    error-feedback table, mirroring the PARAMETER pytree leaf-for-leaf with a
    leading client axis: a ``[N, D]`` array for flat params, a pytree of
    ``[N, *leaf.shape]`` tables for pytree params (vision MLPs), and a single
    empty ``[N, 0]`` array when EF is off (residual element count is the
    trace-time EF flag — see ``ef_enabled``).

    ``params``/``down``/``mom`` are the three legs' compressor scalars —
    pure operand data, so a full compressor swap on any leg re-traces
    nothing. ``down_ref`` is the last broadcast reconstruction (what every
    client currently holds) and ``down_residual`` the SERVER-side
    bidirectional error-feedback residual; both mirror the parameter pytree
    (one copy, not per-client — the broadcast is common) and are carried
    unconditionally so enabling downlink compression is an operand change,
    not a shape change. Under an identity downlink leg both are exact:
    ``down_ref`` equals the last payload bitwise and ``down_residual`` is
    exactly zero.

    ``bits_up``/``bits_down`` meter the CURRENT round only: executors zero
    them at round start, ``account_round`` (and the chain's selection
    billing) add within the round, and the executor emits the totals as the
    per-round [R] meters. Keeping the in-scan meters per-round (a few 1e8
    bits at most, exact in float32 for the 32-bit-granular counts) instead
    of cumulative is what keeps the accounting exact — cumulative sums are
    taken in float64 OUTSIDE the scan (``SweepResult.cumulative_bits``).
    """

    params: CommParams  # uplink leg compressor scalars
    mask: jnp.ndarray  # [N] float32 ∈ {0, 1}
    residual: object  # params-shaped pytree of [N, ...] tables, or [N, 0]
    bits_up: jnp.ndarray  # float32 scalar, THIS round's uplink bits
    bits_down: jnp.ndarray  # float32 scalar, THIS round's downlink bits
    down: CommParams  # downlink leg compressor scalars
    mom: CommParams  # momentum-uplink leg compressor scalars
    down_ref: object  # params-shaped pytree: last broadcast reconstruction
    down_residual: object  # params-shaped pytree: server-side EF residual


def zero_round_bits(comm: CommState) -> CommState:
    """Reset the per-round meters (executors call this at round start)."""
    return comm._replace(bits_up=jnp.zeros_like(comm.bits_up),
                         bits_down=jnp.zeros_like(comm.bits_down))


def ef_enabled(comm: CommState) -> bool:
    """Trace-time error-feedback flag for the UPLINK residual tables,
    encoded in their shapes (an EF-off state carries one empty [N, 0]
    table; shapes are static). The downlink residual is always carried."""
    return tm.tree_size(comm.residual) > 0


def leaf_dims(x) -> tuple:
    """Per-leaf element counts of a parameter pytree — the shape signature
    bits accounting sums closed forms over. Accepts an int (a flat dimension
    ``d``), a tuple of per-leaf dims, or any params pytree."""
    if isinstance(x, int):
        return (x,)
    if isinstance(x, (tuple, list)) and all(isinstance(d, int) for d in x):
        return tuple(x)
    return tm.tree_leaf_dims(x)


def total_dim(x) -> int:
    """Total parameter count of ``x`` (sum over leaves; static)."""
    return sum(leaf_dims(x))


def comm_key(key):
    """The comm PRNG stream for a round key (quantization randomness)."""
    return jax.random.fold_in(key, _COMM_KEY_TAG)


def second_uplink_key(key):
    """The comm stream for a round's SECOND compressed uplink (SAGA's fresh
    gradients, SCAFFOLD's control deltas). The tag value predates the
    registry and stays 1 so existing trajectories remain bitwise intact."""
    return jax.random.fold_in(comm_key(key), _SECOND_UPLINK_TAG)


def downlink_key(key):
    """The comm stream for the round's compressed broadcast (downlink EF).
    Derived UNDER the comm stream so enabling downlink compression never
    disturbs the uplink randomness (identity-downlink bit-exactness)."""
    return jax.random.fold_in(comm_key(key), _DOWNLINK_KEY_TAG)


def second_downlink_key(key):
    """The stream for a round's SECOND broadcast (SCAFFOLD's server
    variate, SSNM's snapshot point) — stateless, no EF chain."""
    return jax.random.fold_in(downlink_key(key), _SECOND_UPLINK_TAG)


def momentum_uplink_key(key):
    """The comm stream for a compressed MOMENTUM uplink (ASG's lookahead
    gradients, SSNM's sampled-negative-momentum gradients), independent of
    the plain uplink stream so momentum compression composes with it."""
    return jax.random.fold_in(comm_key(key), _MOMENTUM_UPLINK_TAG)


def participation_scale(mask, cids):
    """Per-row aggregation weights turning a plain client mean into the
    participant mean: scaleᵢ = m_i · S_rows / Σm, so
    meanᵢ(scaleᵢ·vᵢ) = Σ m_i·v_i / Σm. Under full participation every scale
    is exactly 1.0 — multiplying by it is a bitwise no-op."""
    m = mask[cids].astype(jnp.float32)
    total = jnp.maximum(jnp.sum(m), 1.0)
    return m * (jnp.float32(m.shape[0]) / total)


def uplink_bits_per_client(params: CommParams, d: int):
    """Closed-form uplink bits for ONE compressed [d] LEAF (traced scalar).

    QSGD bills one ℓ₂-norm float per leaf (compression is leaf-wise);
    top-k/rand-k retain k coordinates per leaf, each addressed by a
    ⌈log₂ d_leaf⌉-bit index.
    """
    idx_bits = float(max(1, math.ceil(math.log2(d)))) if d > 1 else 1.0
    k = params.spars_k.astype(jnp.float32)
    return jnp.select(
        [params.comp_id == COMP_IDENTITY, params.comp_id == COMP_QSGD],
        [jnp.float32(32.0 * d), 32.0 + d * (params.qsgd_bits + 1.0)],
        default=k * (32.0 + idx_bits),
    )


def uplink_bits_per_client_tree(params: CommParams, dims):
    """Uplink bits of one compressed parameter PYTREE per client: the sum of
    per-leaf closed forms. ``dims`` is an int, a tuple of leaf dims, or a
    params pytree (see ``leaf_dims``); a flat [D] vector reduces to the
    single-leaf closed form exactly."""
    return sum(uplink_bits_per_client(params, d) for d in leaf_dims(dims))


def downlink_bits_per_client(params: CommParams, dims):
    """Closed-form downlink bits of ONE broadcast pytree per client: the
    wire format is direction-symmetric, so the per-leaf closed forms are the
    uplink's, evaluated at the DOWNLINK leg's params. An identity leg
    reduces to the full-precision 32·Σ_l d_l broadcast exactly (the
    pre-plan hardcoded form)."""
    return sum(uplink_bits_per_client(params, d) for d in leaf_dims(dims))


def selection_round_bits(dims, s_sel: int):
    """(uplink, downlink) bits of one Lemma H.2 two-candidate selection.
    Selection broadcasts stay full-precision: candidates must be evaluated
    at the exact points the chain compares."""
    return 2.0 * 32.0 * s_sel, 2.0 * 32.0 * total_dim(dims) * s_sel


def account_round(comm: CommState, dims, *, up_vectors: int = 0,
                  down_vectors: int = 0, mom_vectors: int = 0) -> CommState:
    """Accumulate one round's bits: S_r participants, each transmitting
    ``up_vectors`` pytrees on the uplink leg and ``mom_vectors`` on the
    momentum leg, and receiving ``down_vectors`` broadcast pytrees billed at
    the downlink leg's closed form. ``dims`` is the parameter pytree itself
    (or its int/tuple dims)."""
    s_r = jnp.sum(comm.mask.astype(jnp.float32))
    up = s_r * up_vectors * uplink_bits_per_client_tree(comm.params, dims)
    if mom_vectors:
        up = up + (s_r * mom_vectors
                   * uplink_bits_per_client_tree(comm.mom, dims))
    down = s_r * down_vectors * downlink_bits_per_client(comm.down, dims)
    return comm._replace(bits_up=comm.bits_up + up,
                         bits_down=comm.bits_down + down)


@scope("uplink")
def uplink(comm: CommState, payload, cids, key, *, ref=None,
           use_ef: bool = True, leg: str = "up"):
    """Compress one batch of per-client uplink pytrees.

    ``payload`` is a pytree whose leaves are [S, ...] (row i = client
    ``cids[i]``'s transmission); a flat [S, D] array is the single-leaf case
    and reproduces the pre-pytree implementation bitwise. ``ref`` is an
    optional reference pytree (the broadcast iterate) — when given, the
    *delta* payload − ref is compressed and the reconstruction ref + C(Δ)
    returned, which is the standard wire format for local-update methods.
    Identity compression short-circuits to the payload itself (bitwise),
    whatever the reference. Error feedback adds the client's residual (a
    params-shaped table pytree) before compression and stores the
    quantization error after — participants only (masked-out clients neither
    transmit nor consume residual). ``leg`` selects the compressor params:
    ``"up"`` (the plain uplink leg) or ``"mom"`` (the momentum leg ASG/SSNM
    ship accelerated gradients on — same residual tables, same kernels,
    independently swappable params). Returns ``(reconstruction, CommState)``.
    """
    if leg not in ("up", "mom"):
        raise ValueError(f"unknown uplink leg {leg!r}; expected 'up'/'mom'")
    params = comm.params if leg == "up" else comm.mom
    delta = tm.tree_sub(payload, ref) if ref is not None else payload

    ef = ef_enabled(comm) and use_ef
    if ef:
        res = jax.tree.map(lambda t: t[cids], comm.residual)
        delta_in = tm.tree_add(delta, res)
    else:
        delta_in = delta

    comp = compressors.compress_tree(delta_in, key, params)

    if ef:
        m = comm.mask[cids].astype(jnp.float32)
        mb = tm.tree_bcast_rows(m, delta_in)  # [S, 1, …, 1] per leaf
        new_res = jax.tree.map(
            lambda mm, di, co, rs: mm * (di - co) + (1.0 - mm) * rs,
            mb, delta_in, comp, res)
        comm = comm._replace(residual=jax.tree.map(
            lambda t, v: t.at[cids].set(v), comm.residual, new_res))

    recon = tm.tree_add(ref, comp) if ref is not None else comp
    # identity returns the payload itself: ref + (payload − ref) round-trips
    # through float addition, but the wire carried the exact payload.
    out = jax.tree.map(
        lambda pl, rc: jnp.where(params.comp_id == COMP_IDENTITY, pl, rc),
        payload, recon)
    return out, comm


@scope("downlink")
def downlink(comm: CommState, payload, key):
    """Compress the round's server→client broadcast with bidirectional
    error feedback.

    ``payload`` is the parameter pytree the server wants every client to
    hold (the iterate, or ASG's lookahead point). The wire carries
    C(payload − down_ref + down_residual) through the SAME leaf-wise
    [S, d_leaf] ravel boundary and compressor kernels as the uplink (S = 1:
    the broadcast is common to all clients), the reconstruction
    down_ref + C(Δ) is what clients compute at this round, and the server
    keeps the quantization error in ``down_residual`` for the next
    broadcast. An identity downlink leg short-circuits bitwise to the
    payload with an exactly-zero residual, so uplink-only plans reproduce
    the uncompressed trajectories bit-for-bit. Returns
    ``(reconstruction, CommState)``.
    """
    params = comm.down
    delta = tm.tree_sub(payload, comm.down_ref)
    delta_in = tm.tree_add(delta, comm.down_residual)
    rows = jax.tree.map(lambda l: l[None], delta_in)
    comp = jax.tree.map(lambda l: jnp.squeeze(l, 0),
                        compressors.compress_tree(rows, key, params))
    is_id = params.comp_id == COMP_IDENTITY
    recon = jax.tree.map(
        lambda pl, rf, co: jnp.where(is_id, pl, rf + co),
        payload, comm.down_ref, comp)
    new_res = jax.tree.map(
        lambda di, co: jnp.where(is_id, jnp.zeros_like(di), di - co),
        delta_in, comp)
    return recon, comm._replace(down_ref=recon, down_residual=new_res)


@scope("downlink")
def downlink_second(comm: CommState, payload, key):
    """Compress a round's SECOND broadcast (SCAFFOLD's server variate c,
    SSNM's snapshot point) on the downlink leg — stateless: no reference,
    no error-feedback chain (the payload is not the iterate the ``down_ref``
    chain tracks). Identity short-circuits to the payload bitwise. Returns
    the reconstruction only; bill it via ``down_vectors``."""
    params = comm.down
    rows = jax.tree.map(lambda l: l[None], payload)
    comp = jax.tree.map(lambda l: jnp.squeeze(l, 0),
                        compressors.compress_tree(rows, key, params))
    return jax.tree.map(
        lambda pl, co: jnp.where(params.comp_id == COMP_IDENTITY, pl, co),
        payload, comp)


@scope("uplink")
def uplink_fused_apply(comm: CommState, payload, cids, key, x, eta, *,
                       ref=None, force_pallas: bool = False):
    """One fused uplink + error-feedback + server-apply round.

    The launch-minimal sibling of ``uplink`` + aggregate + step for
    error-feedback rounds: compression still runs leaf-wise (identical
    randomness and results to ``uplink``), but the masked residual update
    AND the weighted server step then execute as ONE fused kernel pass per
    leaf over the raveled [S, d_leaf] rows
    (``kernels.aggregate.ops.aggregate_apply``) instead of separate
    gather/scatter/mean/axpy launches.

    ``payload`` rows are client transmissions (leaves [S, ...] mirroring the
    ``x`` pytree); ``ref`` selects the wire format exactly as in ``uplink``
    (``None``: the payload itself is the wire delta — global-update methods;
    the broadcast iterate: payload − ref is compressed — local-update
    methods). ``eta`` is the server stepsize folded into the aggregation
    weights as ``scale·(η/S)`` — the exact fold ``base.fused_server_step``
    performs, so the SGD comm round is bitwise identical fused vs unfused on
    kernel backends; pass ``−server_lr`` for iterate-averaging methods
    (x + lr·mean ≡ x − (−lr)·mean, equal to float tolerance).

    EF only (the residual tables are what the fusion saves traffic on);
    callers gate on ``ef_enabled`` and ``ops.use_fused_aggregate``. Returns
    ``(x_new, CommState)`` — bits accounting stays with ``account_round``.
    """
    from repro.kernels.aggregate import ops as agg_ops

    if not ef_enabled(comm):
        raise ValueError(
            "uplink_fused_apply is the error-feedback round path; with EF "
            "off there is no residual table to fuse over — use uplink()")
    params = comm.params
    delta = tm.tree_sub(payload, ref) if ref is not None else payload
    res = jax.tree.map(lambda t: t[cids], comm.residual)
    delta_in = tm.tree_add(delta, res)
    comp = compressors.compress_tree(delta_in, key, params)
    # wire rows entering the server sum: identity short-circuits to the
    # exact delta (matching uplink's bitwise identity contract), every
    # other compressor transmits C(Δ_in)
    agg = jax.tree.map(
        lambda dl, co: jnp.where(params.comp_id == COMP_IDENTITY, dl, co),
        delta, comp)
    m = comm.mask[cids].astype(jnp.float32)
    s = m.shape[0]
    w = participation_scale(comm.mask, cids) * (eta / s)

    treedef = jax.tree.structure(x)
    x_new, res_new = [], []
    for xl, al, dl, cl, rl in zip(
            jax.tree.leaves(x), jax.tree.leaves(agg),
            jax.tree.leaves(delta_in), jax.tree.leaves(comp),
            jax.tree.leaves(res)):
        xn, rn = agg_ops.aggregate_apply(
            xl.reshape(-1), al.reshape(s, -1), cl.reshape(s, -1),
            dl.reshape(s, -1), rl.reshape(s, -1), m, w,
            force_pallas=force_pallas)
        x_new.append(xn.reshape(xl.shape))
        res_new.append(rn.reshape(rl.shape))
    comm = comm._replace(residual=jax.tree.map(
        lambda t, v: t.at[cids].set(v), comm.residual,
        jax.tree.unflatten(treedef, res_new)))
    return jax.tree.unflatten(treedef, x_new), comm


@dataclasses.dataclass(frozen=True)
class Leg:
    """One wire direction of a ``CommPlan``: a compressor + its params.

    ``error_feedback`` sizes the per-client residual tables for the uplink
    and momentum legs (trace-time flag, as before). On the DOWNLINK leg the
    flag is ignored: the server-side residual is one params-sized pytree
    (cheap), so bidirectional EF is always active for lossy downlink
    compressors and exactly zero under identity.
    """

    compressor: str = "identity"  # identity | qsgd | topk | randk
    qsgd_bits: int = 4
    spars_k: int = 4
    error_feedback: bool = False

    def __post_init__(self):
        if self.compressor not in compressors.COMP_IDS:
            raise ValueError(
                f"unknown compressor {self.compressor!r}; "
                f"expected one of {sorted(compressors.COMP_IDS)}")
        if self.qsgd_bits < 1:
            raise ValueError("qsgd_bits must be ≥ 1 (one sign+level bit)")
        if self.spars_k < 1:
            raise ValueError("spars_k must be ≥ 1 (top-k/rand-k keep ≥ 1 "
                             "coordinate)")

    @property
    def name(self) -> str:
        tag = {"identity": "full32",
               "qsgd": f"qsgd{self.qsgd_bits}",
               "topk": f"topk{self.spars_k}",
               "randk": f"randk{self.spars_k}"}[self.compressor]
        if self.error_feedback:
            tag += "+ef"
        return tag

    def params(self) -> CommParams:
        return CommParams(
            comp_id=jnp.asarray(compressors.COMP_IDS[self.compressor],
                                jnp.int32),
            qsgd_bits=jnp.asarray(self.qsgd_bits, jnp.float32),
            spars_k=jnp.asarray(self.spars_k, jnp.int32),
        )

    def _check_dims(self, dims, role: str):
        if self.compressor in ("topk", "randk") and self.spars_k > min(dims):
            raise ValueError(
                f"spars_k={self.spars_k} exceeds the parameter dimension "
                f"{min(dims)} (smallest leaf of {dims}): the sparsifier "
                f"would keep everything while billing MORE than the identity "
                f"compressor — use identity (or a smaller k) instead "
                f"[{role} leg]")


@dataclasses.dataclass(frozen=True)
class CommPlan:
    """Static description of a direction-symmetric communication regime.

    One ``Leg`` per wire direction: ``uplink`` (client→server deltas),
    ``downlink`` (server→client broadcasts, bidirectional EF), and
    ``momentum`` (the uplink leg accelerated methods — ASG/SSNM — ship
    momentum/variance-reduction gradients on; ``None`` reuses the uplink
    leg). All three compress through the same ``lax.switch`` compressor
    table in one compile — every leg's params are executor operands.

    ``participation`` is the per-round client fraction (exactly
    ``max(1, round(frac·N))`` clients are drawn uniformly without
    replacement each round); ``mask_seed`` seeds the mask schedule —
    independent of the run key, so comm schedules are reproducible across
    algorithms.
    """

    uplink: Leg = Leg()
    downlink: Leg = Leg()
    momentum: Optional[Leg] = None
    participation: float = 1.0
    mask_seed: int = 0

    def __post_init__(self):
        if not (0.0 < self.participation <= 1.0):
            raise ValueError("participation must be in (0, 1]")

    @property
    def momentum_leg(self) -> Leg:
        """The effective momentum leg (``momentum`` or the uplink leg)."""
        return self.momentum if self.momentum is not None else self.uplink

    @property
    def name(self) -> str:
        tag = f"up:{self.uplink.name}|down:{self.downlink.name}"
        if self.momentum is not None:
            tag += f"|mom:{self.momentum.name}"
        if self.participation < 1.0:
            tag += f"+part{self.participation:g}"
        return tag

    def clients_per_round(self, num_clients: int) -> int:
        return max(1, int(round(self.participation * num_clients)))

    def round_masks(self, rounds: int, num_clients: int, *, fold: int = 0):
        """[R, N] float32 schedule: exactly ``clients_per_round`` ones per
        row, drawn uniformly without replacement. ``fold`` derives
        independent schedules (e.g. one per sweep seed) from one mask_seed.
        Full participation returns all-ones (no randomness consumed)."""
        if self.participation >= 1.0:
            return jnp.ones((rounds, num_clients), jnp.float32)
        s = self.clients_per_round(num_clients)
        key = jax.random.fold_in(jax.random.PRNGKey(self.mask_seed), fold)

        def one_round(k):
            u = jax.random.uniform(k, (num_clients,))
            ranks = jnp.argsort(jnp.argsort(u))
            return (ranks < s).astype(jnp.float32)

        return jax.vmap(one_round)(jax.random.split(key, rounds))

    def init_state(self, num_clients: int, params_or_dim) -> CommState:
        """Initial ``CommState`` for ``num_clients`` clients over the given
        parameter layout: an int (flat dimension d — the legacy signature)
        or the parameter pytree itself, whose leaf shapes size the
        per-client error-feedback residual tables and the server-side
        downlink reference/residual."""
        template = (jnp.zeros((params_or_dim,), jnp.float32)
                    if isinstance(params_or_dim, int) else params_or_dim)
        dims = leaf_dims(template)
        self.uplink._check_dims(dims, "uplink")
        self.downlink._check_dims(dims, "downlink")
        self.momentum_leg._check_dims(dims, "momentum")
        ef = self.uplink.error_feedback or (
            self.momentum is not None and self.momentum.error_feedback)
        if ef:
            residual = jax.tree.map(
                lambda l: jnp.zeros((num_clients,) + jnp.shape(l),
                                    jnp.float32), template)
        else:
            residual = jnp.zeros((num_clients, 0), jnp.float32)
        return CommState(
            params=self.uplink.params(),
            mask=jnp.ones((num_clients,), jnp.float32),
            residual=residual,
            bits_up=jnp.asarray(0.0, jnp.float32),
            bits_down=jnp.asarray(0.0, jnp.float32),
            down=self.downlink.params(),
            mom=self.momentum_leg.params(),
            down_ref=tm.tree_zeros_like(template),
            down_residual=tm.tree_zeros_like(template),
        )

    def uplink_bits(self, dims) -> float:
        """Bits per client per uplinked pytree (int dim, tuple of leaf
        dims, or params pytree) — evaluates the SAME closed form the
        executors bill (``uplink_bits_per_client_tree``), so reports can
        never desynchronize from the in-scan accounting."""
        return float(uplink_bits_per_client_tree(self.uplink.params(), dims))

    def downlink_bits(self, dims) -> float:
        """Bits per client per broadcast pytree at the downlink leg."""
        return float(downlink_bits_per_client(self.downlink.params(), dims))

    def momentum_bits(self, dims) -> float:
        """Bits per client per momentum-leg uplinked pytree."""
        return float(uplink_bits_per_client_tree(
            self.momentum_leg.params(), dims))


@dataclasses.dataclass(frozen=True)
class CommConfig:
    """Deprecated uplink-only shim over ``CommPlan``.

    Kept for existing configs and reports: it describes ONE compressed
    direction and constructs ``plan()`` — an uplink-only ``CommPlan`` with
    an identity downlink leg — bitwise identical to the pre-plan behaviour
    (the spec.py ``FederatedProblem`` shim is the template). Every executor
    entry point accepts either; new code should build ``CommPlan`` directly.
    """

    compressor: str = "identity"  # identity | qsgd | topk | randk
    qsgd_bits: int = 4
    spars_k: int = 4
    participation: float = 1.0
    error_feedback: bool = False
    mask_seed: int = 0

    def __post_init__(self):
        self.plan()  # Leg/CommPlan validation, same messages as before

    def plan(self) -> CommPlan:
        """The uplink-only ``CommPlan`` this shim describes."""
        return CommPlan(
            uplink=Leg(compressor=self.compressor, qsgd_bits=self.qsgd_bits,
                       spars_k=self.spars_k,
                       error_feedback=self.error_feedback),
            participation=self.participation,
            mask_seed=self.mask_seed,
        )

    @property
    def name(self) -> str:
        tag = self.plan().uplink.name
        if self.participation < 1.0:
            tag += f"+part{self.participation:g}"
        return tag

    def params(self) -> CommParams:
        return self.plan().uplink.params()

    def clients_per_round(self, num_clients: int) -> int:
        return self.plan().clients_per_round(num_clients)

    def round_masks(self, rounds: int, num_clients: int, *, fold: int = 0):
        return self.plan().round_masks(rounds, num_clients, fold=fold)

    def init_state(self, num_clients: int, params_or_dim) -> CommState:
        return self.plan().init_state(num_clients, params_or_dim)

    def uplink_bits(self, dims) -> float:
        return self.plan().uplink_bits(dims)


def masked_keep(mask_rows, new, old):
    """Participants take the new value; masked-out clients keep the old —
    the table-update convention every comm-aware algorithm shares (a bitwise
    no-op selecting ``new`` under full participation). ``new``/``old`` are
    pytrees with [S, ...] leaves; the [S] mask broadcasts leaf-wise."""
    return jax.tree.map(
        lambda n, o: jnp.where(
            mask_rows.reshape(mask_rows.shape + (1,) * (n.ndim - 1)) > 0,
            n, o),
        new, old)


def reject_algo_participation(algo_s: int, algo_name: str):
    """Comm-enabled rounds own participation through the mask schedule; an
    algorithm's own ``s`` would be silently ignored — refuse instead."""
    if algo_s and algo_s > 0:
        raise ValueError(
            f"algorithm {algo_name!r} sets s={algo_s} (its own client "
            f"sampling) but the comm layer is enabled — participation is "
            f"owned by CommConfig.participation (the per-round mask "
            f"schedule); set s=0 on the algorithm and put the fraction in "
            f"the comm config")


def require_comm_leaf(state, algo_name: str):
    """Pre-run check that an algorithm's state CAN carry a comm leaf (the
    friendly error before ``_replace(comm=...)`` would crash on a NamedTuple
    without the field — e.g. ACSA's state)."""
    if not hasattr(state, "comm"):
        raise TypeError(
            f"algorithm {algo_name!r} is not comm-aware: its state has no "
            f"comm leaf (see algorithms.base — comm-aware states declare "
            f"`comm: Optional[object] = None`)")
    return state


def comm_state_or_error(state, algo_name: str) -> Optional[CommState]:
    """Executor-side check that an algorithm honored the comm leaf."""
    comm = getattr(state, "comm", None)
    if comm is None:
        raise TypeError(
            f"algorithm {algo_name!r} is not comm-aware: its round() dropped "
            f"the comm leaf (comm-aware rounds must thread state.comm "
            f"through and account their uplinks)")
    return comm
