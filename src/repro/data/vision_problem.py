"""Nonconvex federated vision problems (paper §6 Table 3 / Fig. 2 substrate).

Builds the ``vision`` ``ProblemSpec`` family over a small MLP/logistic
classifier on the synthetic prototype-image datasets, partitioned with the
paper's "X% homogeneous" scheme. Parameters are pytrees — the same Algos 2–7
run unchanged on these (that is the point of the pytree-based core), and
since PR 4 the comm subsystem (compressed uplinks, error feedback, bits
accounting) runs leaf-wise on them too.

``vision_spec`` is the primary constructor: specs built at different
``homogeneous_frac`` (the Table 3 heterogeneity axis) share one static
structure, so ``spec.stack_specs`` + ``core.sweep.run_sweep(problems=...)``
runs the whole grid through ONE compiled executor (``benchmarks/
table3_vision.py``). ``make_vision_problem`` keeps the legacy
``(problem, accuracy, init_params)`` signature as a spec-backed shim.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import tree_math as tm
from repro.data import partition, synthetic_vision
from repro.data.problems import problem_from_spec
from repro.data.spec import FAMILY_VISION, ProblemSpec, _consts, _vision_apply


def _mlp_init(key, dims):
    params = {}
    ks = jax.random.split(key, len(dims) - 1)
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        params[f"w{i}"] = jax.random.normal(ks[i], (a, b)) * (1.0 / a) ** 0.5
        params[f"b{i}"] = jnp.zeros((b,))
    return params


def _mlp_apply(params, x):
    return _vision_apply(params, x)


def vision_spec(
    key,
    *,
    num_clients: int = 5,
    homogeneous_frac: float = 0.5,
    num_classes: int = 10,
    per_class: int = 200,
    side: int = 14,
    hidden: int = 64,
    batch: int = 32,
    l2: float = 1e-4,
    seed: int = 0,
    name: str = "vision",
) -> ProblemSpec:
    """The Table 3 problem as a ``vision``-family spec.

    ``key`` seeds the deterministic MLP init baked into ``x0``; ``seed``
    drives the synthetic dataset + partition. The default ``name`` is
    deliberately constant-free so a ``homogeneous_frac`` grid of specs
    shares one treedef (and therefore one compiled executor) — only ARRAY
    leaves (the shards) vary across the grid.
    """
    data = synthetic_vision.make_prototype_images(
        num_classes=num_classes, per_class=per_class, side=side, seed=seed)
    cx, cy = partition.shuffled_heterogeneity(
        data, homogeneous_frac=homogeneous_frac, num_clients=num_clients,
        seed=seed)
    return vision_spec_from_shards(
        key, cx, cy, num_classes=num_classes, hidden=hidden, batch=batch,
        l2=l2, name=name)


def vision_spec_from_shards(key, features, labels, *, num_classes: int,
                            hidden: int, batch: int, l2: float = 1e-4,
                            name: str = "vision") -> ProblemSpec:
    """A ``vision``-family spec over pre-partitioned shards: features
    [N, n_i, d], integer labels [N, n_i]; an MLP d-hidden-classes (no hidden
    layer when ``hidden`` is 0) initialized from ``key``."""
    features = jnp.asarray(features)
    labels = jnp.asarray(labels, jnp.int32)
    n_clients, _, d = features.shape
    dims = (d, hidden, num_classes) if hidden else (d, num_classes)

    x0 = _mlp_init(key, dims)
    return ProblemSpec(
        family=FAMILY_VISION, num_clients=n_clients,
        dim=int(tm.tree_size(x0)), batch=batch, arch=tuple(dims), name=name,
        data=dict(features=features, labels=labels),
        consts=_consts(mu=l2, beta=10.0),  # rough β, as the legacy builder
        x0=x0, x_star=tm.tree_zeros_like(x0),
    )


def vision_accuracy(spec: ProblemSpec):
    """Pooled classification accuracy on the spec's shards — ``fn(params)``."""
    features = spec.data["features"]
    labels = spec.data["labels"]
    d = features.shape[-1]

    def accuracy(params):
        logits = _mlp_apply(params, features.reshape(-1, d))
        pred = jnp.argmax(logits, -1)
        return jnp.mean((pred == labels.reshape(-1)).astype(jnp.float32))

    return accuracy


def make_vision_problem(
    key,
    *,
    num_clients: int = 5,
    homogeneous_frac: float = 0.5,
    num_classes: int = 10,
    per_class: int = 200,
    side: int = 14,
    hidden: int = 64,
    batch: int = 32,
    l2: float = 1e-4,
    seed: int = 0,
):
    """Returns (FederatedProblem, accuracy_fn, init_params) — spec-backed.

    The shim's oracles ARE the vision spec's family oracles, so the executor
    operand path and the legacy closure path (``problems.without_spec``) run
    identical math; the returned problem carries its spec, so Table 3
    harnesses batch it through ``run_sweep(problems=...)``. ``init_params``
    keeps the legacy behavior of a fresh MLP init per PRNG key (the spec's
    own ``x0`` is the init at the builder's ``key``).
    """
    spec = vision_spec(
        key, num_clients=num_clients, homogeneous_frac=homogeneous_frac,
        num_classes=num_classes, per_class=per_class, side=side,
        hidden=hidden, batch=batch, l2=l2, seed=seed)
    problem = problem_from_spec(
        spec, name=f"vision(hom={homogeneous_frac},hidden={hidden})")
    dims = spec.arch

    def init_params(rng):
        return _mlp_init(rng, dims)

    return problem, vision_accuracy(spec), init_params
