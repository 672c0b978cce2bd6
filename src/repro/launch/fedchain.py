"""FedChain as a first-class distributed-training feature.

Mapping (DESIGN.md §2): a *client* is a client-group of the mesh — the "pod"
axis on the multi-pod mesh, or a dedicated "client" axis on a single-pod FL
mesh. The paper's phases become collective schedules:

  * local phase  (A_local = FedAvg):  each client group holds its own replica
    of the parameters (leading [C] axis sharded over the client axis) and runs
    ``vmap``-ed train steps — data-parallel gradient reductions stay *inside*
    the group, so a local step emits ZERO cross-group collective bytes.
  * round boundary: one cross-group parameter average (all-reduce over the
    client axis) — optionally through the fused ``chain_aggregate`` kernel.
  * selection (Lemma H.2): per-client loss on a held-out probe batch for both
    candidates, one scalar all-reduce, argmin.
  * global phase (A_global = SGD/ASG): standard synchronous data-parallel
    steps over the full mesh every step.

The §Roofline collective-bytes comparison between these programs is the
paper's round-complexity saving expressed in TPU link traffic.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import model_zoo, transformer
from repro.optim import Optimizer
from repro.sharding import RuleSet, param_specs


def make_fl_mesh(clients: int = 4, data: int = 4, model: int = 16):
    """Single-pod FL mesh: the 16-way data axis split into client × data."""
    from repro.launch.mesh import make_mesh

    return make_mesh(
        (clients, data, model), ("client", "data", "model"))


def client_axis_name(mesh) -> str:
    return "client" if "client" in mesh.axis_names else "pod"


def num_clients(mesh) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape))[client_axis_name(mesh)]


@dataclasses.dataclass(frozen=True)
class FedChainConfig:
    local_rounds: int = 8  # rounds of A_local
    local_steps: int = 16  # K: local steps per round (between syncs)
    global_steps: int = 0  # remaining synchronous steps (0 => run until budget)
    server_lr: float = 1.0
    selection_enabled: bool = True


def _stack_specs(specs, client_axis):
    """Prepend the client axis to every leaf PartitionSpec."""
    return jax.tree.map(
        lambda s: P(client_axis, *s), specs, is_leaf=lambda s: isinstance(s, P))


def fedchain_shardings(cfg, mesh, ruleset: Optional[RuleSet] = None):
    """(stacked_param_shardings, per_client_batch_sharding builder)."""
    rs = ruleset or RuleSet(mesh)
    c_ax = client_axis_name(mesh)
    shapes = jax.eval_shape(
        lambda k: transformer.init_model(cfg, k), jax.ShapeDtypeStruct((2,), jnp.uint32))
    specs = param_specs(shapes, rs)
    stacked = _stack_specs(specs, c_ax)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), stacked,
                        is_leaf=lambda s: isinstance(s, P))


def broadcast_to_clients(params, n_clients: int):
    """Replicate server params into the [C, ...] stacked layout."""
    return jax.tree.map(lambda t: jnp.broadcast_to(t[None], (n_clients,) + t.shape), params)


def make_local_round(cfg, optimizer: Optimizer, fl: FedChainConfig, *,
                     n_clients: int, moe_groups: int = 1):
    """One A_local (FedAvg) round: ``local_steps`` per-client SGD steps with
    NO cross-client communication, then a cross-client parameter average.

    client_params/opt: [C, ...]; batches: [local_steps, C, b, ...].
    """
    step = model_zoo.make_train_step(cfg, optimizer, moe_groups=moe_groups)

    def per_client_steps(params, opt_state, batches):
        def body(carry, batch):
            p, o = carry
            p, o, m = step(p, o, batch)
            return (p, o), m["loss"]

        (params, opt_state), losses = jax.lax.scan(body, (params, opt_state), batches)
        return params, opt_state, jnp.mean(losses)

    def local_round(client_params, client_opt, batches):
        # vmap over the client axis: gradient reductions stay within a client
        new_p, new_o, losses = jax.vmap(per_client_steps, in_axes=(0, 0, 1))(
            client_params, client_opt, batches)
        # round boundary: FedAvg server step x <- (1-slr)x + slr*mean_c(y_c)
        mean_p = jax.tree.map(lambda t: jnp.mean(t, axis=0), new_p)
        if fl.server_lr != 1.0:
            old_mean = jax.tree.map(lambda t: jnp.mean(t, axis=0), client_params)
            mean_p = jax.tree.map(
                lambda o, n: ((1.0 - fl.server_lr) * o.astype(jnp.float32)
                              + fl.server_lr * n.astype(jnp.float32)).astype(n.dtype),
                old_mean, mean_p)
        new_client_p = jax.tree.map(
            lambda t: jnp.broadcast_to(t[None], (n_clients,) + t.shape), mean_p)
        return new_client_p, new_o, jnp.mean(losses)

    return local_round


def make_local_steps_only(cfg, optimizer: Optimizer, fl: FedChainConfig, *,
                          moe_groups: int = 1):
    """The inner local phase WITHOUT the sync (for dry-run collective
    accounting: this program must contain no cross-client collectives)."""
    step = model_zoo.make_train_step(cfg, optimizer, moe_groups=moe_groups)

    def local_steps(client_params, client_opt, batches):
        def per_client(params, opt_state, bs):
            def body(carry, batch):
                p, o = carry
                p, o, m = step(p, o, batch)
                return (p, o), m["loss"]

            (params, opt_state), losses = jax.lax.scan(body, (params, opt_state), bs)
            return params, opt_state, jnp.mean(losses)

        return jax.vmap(per_client, in_axes=(0, 0, 1))(client_params, client_opt, batches)

    return local_steps


def make_sync_step(n_clients: int, *, server_lr: float = 1.0, use_kernel: bool = False):
    """The round-boundary cross-client average (the only cross-group collective)."""

    def sync(client_params):
        if use_kernel:
            from repro.kernels.aggregate import ops as agg_ops

            mean_p = jax.tree.map(lambda t: agg_ops.mean_over_clients(t), client_params)
        else:
            mean_p = jax.tree.map(lambda t: jnp.mean(t, axis=0), client_params)
        return jax.tree.map(
            lambda t: jnp.broadcast_to(t[None], (n_clients,) + t.shape), mean_p)

    return sync


def make_selection_step(cfg, *, moe_groups: int = 1):
    """Lemma H.2 at scale: pick argmin of probe-batch loss between the
    pre-phase params and the local-phase output (both [C, ...])."""
    eval_loss = model_zoo.make_eval_loss(cfg, moe_groups=moe_groups)

    def select(cand_a, cand_b, probe_batches):
        la = jnp.mean(jax.vmap(eval_loss)(cand_a, probe_batches))
        lb = jnp.mean(jax.vmap(eval_loss)(cand_b, probe_batches))
        pick_a = la <= lb
        chosen = jax.tree.map(lambda a, b: jnp.where(pick_a, a, b), cand_a, cand_b)
        return chosen, pick_a, (la, lb)

    return select


def make_global_step(cfg, optimizer: Optimizer, *, moe_groups: int = 1):
    """A_global: plain synchronous data-parallel step over the full mesh."""
    return model_zoo.make_train_step(cfg, optimizer, moe_groups=moe_groups)
