"""Closure-free problem specifications: problems as executor OPERANDS.

``ProblemSpec`` is the data-driven redesign of the problem layer: a problem
is a registered JAX pytree whose *dynamic* content is arrays only — curvature
``A``, client offsets ``b_i``/``δ_i``, data shards ``X, y``, and the paper's
constants (μ, β, ζ, ζ_F, σ, σ_F, F*) as array leaves — plus a small *static*
part (the family tag, client/dimension counts, the minibatch size, the
perturbation-base id, the vision family's layer widths). Oracles are
dispatched through one family table keyed by the static tag
(``lax.switch``-style: the dispatch is resolved at trace time because the
tag is pytree metadata, so there is exactly one branch per family, never one
per instance).

The family table (see ``FAMILIES``):

  * ``quadratic`` — strongly convex federated quadratic, exact ζ; flat [D]
    params (data: per-client curvature/offsets).
  * ``perturbed`` — F_i = base(x) + ζ⟨u_i, x⟩ over a registered base id
    (general convex / PL); flat [D] params.
  * ``logreg``    — L2 logistic regression on data shards; flat [D] params.
  * ``vision``    — nonconvex MLP classification on synthetic image shards
    (paper Table 3): params are a PYTREE of layer weights/biases whose
    widths live in the static ``arch`` metadata, so the whole
    "X% homogeneous" heterogeneity grid (``data.vision_problem``) shares one
    compiled executor and batches through ``run_sweep(problems=...)`` —
    including ``comm=`` (the comm layer is leaf-wise).

Why: the executors in ``core.runner``/``core.chain``/``core.sweep`` compile
once per cache key. With the legacy closure problems (``data.problems``),
arrays were *closed over* Python callables, so the cache key had to be the
instance identity — every (ζ, σ, instance) point of the Tables 1–4 grids
re-traced. A ``ProblemSpec`` instead rides INTO the compiled executor as an
operand: the cache key is ``cache_key()`` (family tag + static fields + leaf
shapes/dtypes, never instance identity), so

  * re-running any same-shaped instance reuses the compile (warm ζ grids),
  * ``stack_specs`` batches a whole ζ × σ × family-instance grid into one
    stacked spec that ``core.sweep.run_sweep(problems=...)`` vmaps through a
    single compiled call, and
  * the executor cache stores ``(key, fn)`` only — no problem objects are
    pinned, so client data shards die with their last user reference.

Interface: a spec duck-types the oracle surface the algorithms and executors
use — ``num_clients`` (static), ``grad_oracle(x, i, key)``,
``value_oracle(x, i, key)``, ``client_loss(x, i)``, ``global_loss(x)``,
``init_params(key)`` and the constants — so Algos 2–7 run unchanged on a
traced spec. ``data.problems`` keeps ``FederatedProblem`` as a thin
deprecation shim wrapping a spec (bit-exact with the spec path — tested).

Noise handling: σ and σ_F are *operands* (a noise grid must not re-trace),
so the oracles add noise unconditionally; at σ = 0 the added term is exactly
``0.0 · n`` which is the float zero, keeping σ = 0 runs bitwise equal to the
legacy conditional-noise closures.
"""
from __future__ import annotations

import dataclasses
import hashlib
import warnings
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tree_math as tm

# ---------------------------------------------------------------------------
# the family table
# ---------------------------------------------------------------------------

FAMILY_QUADRATIC = "quadratic"
FAMILY_PERTURBED = "perturbed"
FAMILY_LOGREG = "logreg"
FAMILY_VISION = "vision"

CONST_KEYS = ("mu", "beta", "zeta", "zeta_f", "sigma", "sigma_f", "f_star")


class _Family(NamedTuple):
    """One row of the oracle dispatch table (all take the spec first)."""

    grad: Callable  # (spec, x, i, key) -> grad
    value: Callable  # (spec, x, i, key) -> scalar
    client_loss: Callable  # (spec, x, i) -> scalar
    global_loss: Callable  # (spec, x) -> scalar


# -- quadratic: F_i(x) = 0.5 x^T A_i x − b_i^T x (shared/spread curvature) --
#
# Inner products are written sum(b * x), not jnp.dot: XLA:CPU lowers a
# BATCHED dot (GEMV) with a batch-size-dependent reduction blocking, which
# would make vmapped grids of different batch sizes — in particular the
# device-sharded sweep (repro.dist), whose per-shard batch is 1/n_dev of
# the global one — differ from the single-device engine in the last ulp.
# Elementwise-multiply-then-sum lowers to a batch-invariant row reduction,
# keeping sharded and vmapped sweeps bitwise identical (tested).

def _quad_client_loss(spec, x, i):
    d = spec.data
    return 0.5 * jnp.sum(d["a_i"][i] * x**2) - jnp.sum(d["b"][i] * x)


def _quad_global_loss(spec, x):
    d = spec.data
    return 0.5 * jnp.sum(d["a_bar"] * x**2) - jnp.sum(d["b_bar"] * x)


def _quad_grad(spec, x, i, key):
    d = spec.data
    g = d["a_i"][i] * x - d["b"][i]
    noise = jax.random.normal(key, (spec.dim,))
    return g + (spec.sigma / jnp.sqrt(spec.dim)) * noise


def _quad_value(spec, x, i, key):
    v = _quad_client_loss(spec, x, i)
    return v + spec.sigma_f * jax.random.normal(key, ())


# -- perturbed: F_i(x) = base(x) + ζ⟨u_i, x⟩, Σu_i = 0 ----------------------
#
# The base objective is a *registered* callable addressed by the static
# ``base_id`` tag — the only non-array ingredient of any family, kept out of
# the dynamic data so specs stay arrays-only pytrees.

_BASE_REGISTRY: dict = {}


def register_base(name: str, fn: Callable, *, overwrite: bool = False):
    """Register a perturbation base objective under a static id.

    The id is spec metadata (part of the executor cache key): two specs with
    the same id share compiled executors, so the registered function must be
    pure and stable for the life of the process.
    """
    if not overwrite and name in _BASE_REGISTRY and _BASE_REGISTRY[name] is not fn:
        raise ValueError(f"base id {name!r} is already registered; pass "
                         f"overwrite=True to replace it")
    _BASE_REGISTRY[name] = fn
    return name


def _fingerprint_value(v) -> bytes:
    """A value-sensitive fingerprint for closure cells / defaults: arrays
    hash by their full bytes (repr truncates large arrays, which would
    conflate different data), everything else by repr."""
    try:
        arr = np.asarray(v)
        if arr.dtype != object:
            return (arr.tobytes() + str(arr.shape).encode()
                    + str(arr.dtype).encode())
    except Exception:
        pass
    return repr(v).encode()


def base_id_for(fn: Callable) -> str:
    """Auto-register a base callable, deduplicating by code AND data
    identity.

    Two functions with identical bytecode, constants, captured closure
    values and defaults get the SAME id (so re-building a problem in a loop
    reuses one compiled executor); closures over *different* values — e.g.
    a parameterized base built in a loop — get distinct ids, as do distinct
    functions sharing a qualname.
    """
    if isinstance(fn, str):
        if fn not in _BASE_REGISTRY:
            raise KeyError(f"unknown base id {fn!r}; register_base() it first")
        return fn
    code = getattr(fn, "__code__", None)
    if code is None:
        raise TypeError(f"base must be a plain function, got {type(fn)}")
    h = hashlib.sha1(code.co_code + repr(code.co_consts).encode())
    for cell in fn.__closure__ or ():
        h.update(_fingerprint_value(cell.cell_contents))
    for default in fn.__defaults__ or ():
        h.update(_fingerprint_value(default))
    name = f"fn:{getattr(fn, '__qualname__', 'base')}:{h.hexdigest()[:12]}"
    _BASE_REGISTRY.setdefault(name, fn)
    return name


def _logcosh_base(x):
    # 1-smooth, convex, minimized at 0 with value 0
    return jnp.sum(jnp.log(jnp.cosh(x)))


def _pl_sin2_base(x):
    # classic PL-but-nonconvex: μ = 1/32, β = 8
    return jnp.sum(x**2 + 3.0 * jnp.sin(x) ** 2)


register_base("logcosh", _logcosh_base)
register_base("pl_sin2", _pl_sin2_base)


def _pert_base(spec):
    return _BASE_REGISTRY[spec.base_id]


def _pert_client_loss(spec, x, i):
    # sum(u*x), not dot: batch-invariant lowering (see the quadratic note)
    return _pert_base(spec)(x) + spec.zeta * jnp.sum(spec.data["u"][i] * x)


def _pert_global_loss(spec, x):
    return _pert_base(spec)(x)


def _pert_grad(spec, x, i, key):
    g = jax.grad(_pert_base(spec))(x) + spec.zeta * spec.data["u"][i]
    noise = jax.random.normal(key, (spec.dim,))
    return g + (spec.sigma / jnp.sqrt(spec.dim)) * noise


def _pert_value(spec, x, i, key):
    v = _pert_client_loss(spec, x, i)
    return v + spec.sigma_f * jax.random.normal(key, ())


# -- logreg: L2-regularized logistic regression on data shards --------------

def _logreg_loss_on(spec, w, X, y):
    logits = X @ w
    # numerically stable BCE-with-logits (same op order as the legacy closure)
    per = (jnp.maximum(logits, 0.0) - logits * y
           + jnp.log1p(jnp.exp(-jnp.abs(logits))))
    return jnp.mean(per) + 0.5 * spec.mu * jnp.sum(w**2)  # μ IS the L2 weight


def _logreg_client_loss(spec, w, i):
    d = spec.data
    return _logreg_loss_on(spec, w, d["features"][i], d["labels"][i])


def _logreg_global_loss(spec, w):
    d = spec.data
    losses = jax.vmap(
        lambda X, y: _logreg_loss_on(spec, w, X, y))(d["features"], d["labels"])
    return jnp.mean(losses)


def _logreg_batch(spec, i, key):
    d = spec.data
    n_per = d["features"].shape[1]
    idx = jax.random.randint(key, (spec.batch,), 0, n_per)
    # one (client, row) gather: ``features[i][idx]`` under a vmap over
    # clients gathers each client's whole shard before picking the rows
    return d["features"][i, idx], d["labels"][i, idx]


def _logreg_grad(spec, w, i, key):
    X, y = _logreg_batch(spec, i, key)
    return jax.grad(_logreg_loss_on, argnums=1)(spec, w, X, y)


def _logreg_value(spec, w, i, key):
    X, y = _logreg_batch(spec, i, key)
    v = _logreg_loss_on(spec, w, X, y)
    return v + spec.sigma_f * jax.random.normal(key, ())


# -- vision: nonconvex MLP classification on synthetic image shards ---------
#
# The Table 3 family: parameters are a PYTREE (layer weights/biases, the
# layer widths recorded in the static ``arch`` metadata), client data are
# image shards from ``data.synthetic_vision`` partitioned with the paper's
# "X% homogeneous" scheme. μ doubles as the L2 weight (like logreg);
# softmax cross-entropy + L2 is the objective. The forward pass derives its
# depth from the params pytree structure — static under trace, so one
# compiled executor serves every same-arch instance (a whole
# heterogeneity grid).

def _vision_apply(params, x):
    n = len(params) // 2
    h = x
    for i in range(n):
        h = h @ params[f"w{i}"] + params[f"b{i}"]
        if i < n - 1:
            h = jax.nn.relu(h)
    return h


def _vision_loss_on(spec, params, X, y):
    logits = _vision_apply(params, X)
    ls = jax.nn.log_softmax(logits)
    nll = -jnp.mean(jnp.take_along_axis(ls, y[:, None], axis=1))
    reg = 0.5 * spec.mu * sum(jnp.sum(p**2) for p in jax.tree.leaves(params))
    return nll + reg


def _vision_client_loss(spec, params, i):
    d = spec.data
    return _vision_loss_on(spec, params, d["features"][i], d["labels"][i])


def _vision_global_loss(spec, params):
    d = spec.data
    losses = jax.vmap(
        lambda X, y: _vision_loss_on(spec, params, X, y)
    )(d["features"], d["labels"])
    return jnp.mean(losses)


def _vision_batch(spec, i, key):
    d = spec.data
    n_per = d["features"].shape[1]
    idx = jax.random.randint(key, (spec.batch,), 0, n_per)
    return d["features"][i, idx], d["labels"][i, idx]


def _vision_grad(spec, params, i, key):
    X, y = _vision_batch(spec, i, key)
    return jax.grad(_vision_loss_on, argnums=1)(spec, params, X, y)


def _vision_value(spec, params, i, key):
    X, y = _vision_batch(spec, i, key)
    v = _vision_loss_on(spec, params, X, y)
    return v + spec.sigma_f * jax.random.normal(key, ())


FAMILIES: dict = {
    FAMILY_QUADRATIC: _Family(_quad_grad, _quad_value,
                              _quad_client_loss, _quad_global_loss),
    FAMILY_PERTURBED: _Family(_pert_grad, _pert_value,
                              _pert_client_loss, _pert_global_loss),
    FAMILY_LOGREG: _Family(_logreg_grad, _logreg_value,
                           _logreg_client_loss, _logreg_global_loss),
    FAMILY_VISION: _Family(_vision_grad, _vision_value,
                           _vision_client_loss, _vision_global_loss),
}


# ---------------------------------------------------------------------------
# the spec pytree
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ProblemSpec:
    """A federated problem as pure data (a registered JAX pytree).

    Dynamic (pytree leaves — executor operands, batchable with vmap):
      ``data``    family-specific arrays (see the family builders),
      ``consts``  the paper's constants as float32 scalars
                  (μ, β, ζ, ζ_F, σ, σ_F, F*; F* is 0 when unknown —
                  see ``f_star_known``),
      ``x0``      the deterministic initial point,
      ``x_star``  a global optimum (zeros when unknown — ``x_star_known``).

    Static (pytree metadata — part of every executor cache key):
      ``family`` / ``num_clients`` / ``dim`` / ``base_id`` / ``batch`` /
      ``arch`` (layer widths of the vision family's MLP — input, hidden…,
      classes; ``()`` elsewhere) / ``f_star_known`` / ``x_star_known`` /
      ``name``.

    The same spec type serves unbatched instances and stacked grids: a spec
    produced by ``stack_specs`` simply has a leading axis on every leaf.
    """

    # static metadata
    family: str
    num_clients: int
    dim: int
    base_id: str = ""
    batch: int = 0
    arch: tuple = ()
    f_star_known: bool = False
    x_star_known: bool = False
    name: str = "spec"
    # dynamic leaves
    data: dict = dataclasses.field(default_factory=dict)
    consts: dict = dataclasses.field(default_factory=dict)
    x0: Optional[jnp.ndarray] = None
    x_star: Optional[jnp.ndarray] = None

    # this attribute is how the executors recognize a spec without importing
    # this module (no isinstance — keeps core free of data-layer imports)
    is_problem_spec = True

    # -- oracle surface (duck-types FederatedProblem) ----------------------
    def grad_oracle(self, x, i, key):
        return FAMILIES[self.family].grad(self, x, i, key)

    def value_oracle(self, x, i, key):
        return FAMILIES[self.family].value(self, x, i, key)

    def client_loss(self, x, i):
        return FAMILIES[self.family].client_loss(self, x, i)

    def global_loss(self, x):
        return FAMILIES[self.family].global_loss(self, x)

    def init_params(self, key):
        del key  # deterministic init, as the legacy builders
        return self.x0

    # -- constants ---------------------------------------------------------
    @property
    def mu(self):
        return self.consts["mu"]

    @property
    def beta(self):
        return self.consts["beta"]

    @property
    def zeta(self):
        return self.consts["zeta"]

    @property
    def zeta_f(self):
        return self.consts["zeta_f"]

    @property
    def sigma(self):
        return self.consts["sigma"]

    @property
    def sigma_f(self):
        return self.consts["sigma_f"]

    @property
    def f_star(self):
        """F(x*) when known, else None (mirrors the shim's Optional field)."""
        return self.consts["f_star"] if self.f_star_known else None

    @property
    def f_star_leaf(self):
        """The F* OPERAND the executors subtract — 0.0 when unknown, so
        histories of unknown-F* problems are raw objective values."""
        return self.consts["f_star"]

    # -- conveniences ------------------------------------------------------
    def kappa(self):
        mu = float(self.consts["mu"])
        return float(self.consts["beta"]) / mu if mu > 0 else float("inf")

    def suboptimality(self, params):
        f = self.global_loss(params)
        if not self.f_star_known:
            warnings.warn(
                f"problem {self.name!r} has no known F*: suboptimality() "
                f"returns the RAW objective F(x) (F* treated as 0). Solve or "
                f"supply f_star for true gaps.", stacklevel=2)
            return f
        return f - self.consts["f_star"]

    def global_grad(self, params):
        return jax.grad(self.global_loss)(params)

    def delta(self, x0):
        """Initial suboptimality gap Δ (Assumption B.9)."""
        return float(self.suboptimality(x0))

    def dist_sq(self, x0):
        """Initial distance D² (Assumption B.10), if x* is known."""
        if not self.x_star_known:
            return None
        return float(tm.tree_sq_norm(tm.tree_sub(x0, self.x_star)))

    # -- executor cache identity -------------------------------------------
    def cache_key(self):
        """Structural identity: family/static tags + leaf shapes & dtypes.

        Deliberately EXCLUDES array values and object identity — any
        same-shaped instance of the family reuses the compiled executor.
        """
        leaves, treedef = jax.tree_util.tree_flatten(self)
        return (treedef, tuple(
            (jnp.shape(l), jnp.result_type(l).name) for l in leaves))


jax.tree_util.register_dataclass(
    ProblemSpec,
    data_fields=["data", "consts", "x0", "x_star"],
    meta_fields=["family", "num_clients", "dim", "base_id", "batch", "arch",
                 "f_star_known", "x_star_known", "name"],
)


def is_spec(obj) -> bool:
    return getattr(obj, "is_problem_spec", False)


def _consts(mu=0.0, beta=1.0, zeta=0.0, zeta_f=0.0, sigma=0.0, sigma_f=0.0,
            f_star=0.0):
    vals = dict(mu=mu, beta=beta, zeta=zeta, zeta_f=zeta_f, sigma=sigma,
                sigma_f=sigma_f, f_star=f_star)
    return {k: jnp.asarray(0.0 if vals[k] is None else vals[k], jnp.float32)
            for k in CONST_KEYS}


def stack_specs(specs: Sequence[ProblemSpec]) -> ProblemSpec:
    """Stack same-family, same-shape specs into ONE spec with a leading
    problem axis on every leaf — the operand ``run_sweep(problems=...)``
    vmaps over. Static metadata must match exactly (it is the treedef)."""
    specs = list(specs)
    if not specs:
        raise ValueError("stack_specs needs at least one spec")
    td0 = jax.tree_util.tree_structure(specs[0])
    for s in specs[1:]:
        td = jax.tree_util.tree_structure(s)
        if td != td0:
            raise ValueError(
                f"cannot stack specs with different static structure:\n"
                f"  {td0}\n  {td}\n(same family, clients, dim, base and "
                f"batch are required — a grid varies ARRAY leaves only)")
    shapes0 = [jnp.shape(l) for l in jax.tree_util.tree_leaves(specs[0])]
    for s in specs[1:]:
        shapes = [jnp.shape(l) for l in jax.tree_util.tree_leaves(s)]
        if shapes != shapes0:
            raise ValueError("cannot stack specs with different leaf shapes")
    return jax.tree.map(lambda *xs: jnp.stack(xs), *specs)


def spec_count(spec: ProblemSpec) -> int:
    """Leading problem-axis length of a stacked spec (1 for a plain spec)."""
    mu = spec.consts["mu"]
    return int(mu.shape[0]) if jnp.ndim(mu) > 0 else 1


# ---------------------------------------------------------------------------
# family builders (the spec-native constructors)
# ---------------------------------------------------------------------------

def _spread_directions(key, num_clients, dim):
    """Unit-norm directions u_i with Σ u_i = 0 and max ||u_i|| = 1."""
    u = jax.random.normal(key, (num_clients, dim))
    u = u - jnp.mean(u, axis=0, keepdims=True)
    norms = jnp.linalg.norm(u, axis=1)
    u = u / jnp.maximum(jnp.max(norms), 1e-12)
    return u


def quadratic_spec(
    key,
    *,
    num_clients: int = 8,
    dim: int = 16,
    mu: float = 0.1,
    beta: float = 1.0,
    zeta: float = 0.0,
    sigma: float = 0.0,
    sigma_f: float = 0.0,
    init_scale: float = 5.0,
    curvature_spread: float = 0.0,
    name: str = "quadratic",
) -> ProblemSpec:
    """Strongly convex federated quadratic with *exact* ζ, as a spec.

    Same construction as the legacy ``problems.quadratic_problem`` (shared
    A = diag(eigs in [μ, β]); b_i = b̄ + ζ·u_i with Σu_i = 0, max||u_i|| = 1,
    optional curvature spread); see that docstring for the ζ/ζ_F semantics.
    The default ``name`` is deliberately constant-free so a ζ/σ grid of specs
    shares one treedef (and therefore one compiled executor).
    """
    k_eig, k_b, k_u, k_c, k_x0 = jax.random.split(key, 5)
    eigs = jnp.linspace(mu, beta, dim)
    b_bar = jax.random.normal(k_b, (dim,))
    u = _spread_directions(k_u, num_clients, dim)
    b = b_bar[None, :] + zeta * u  # [N, dim]

    if curvature_spread > 0:
        d_i = _spread_directions(k_c, num_clients, dim)  # Σ = 0, max-norm 1
        scale_i = jnp.clip(1.0 + curvature_spread * d_i, 0.2, 2.0)
        a_i = eigs[None, :] * scale_i  # [N, dim]
        a_bar = jnp.mean(a_i, axis=0)
    else:
        a_i = jnp.broadcast_to(eigs[None, :], (num_clients, dim))
        a_bar = eigs

    x_star = b_bar / a_bar
    f_star = float(0.5 * jnp.sum(a_bar * x_star**2) - jnp.dot(b_bar, x_star))

    x0_dir = jax.random.normal(k_x0, (dim,))
    x0 = x_star + init_scale * x0_dir / jnp.linalg.norm(x0_dir)

    # ζ_F on the init_scale ball (scale hint, as the legacy builder)
    zeta_f = float(zeta * (init_scale + jnp.linalg.norm(x_star)))

    zeta_eff = zeta
    if curvature_spread > 0:
        radius = init_scale + float(jnp.linalg.norm(x_star))
        spread_norm = float(jnp.max(jnp.linalg.norm(a_i - a_bar[None], axis=1)))
        zeta_eff = zeta + spread_norm * radius

    return ProblemSpec(
        family=FAMILY_QUADRATIC, num_clients=num_clients, dim=dim,
        f_star_known=True, x_star_known=True, name=name,
        data=dict(a_i=jnp.asarray(a_i), a_bar=jnp.asarray(a_bar),
                  b=jnp.asarray(b), b_bar=jnp.asarray(b_bar)),
        consts=_consts(mu=mu, beta=beta, zeta=zeta_eff, zeta_f=zeta_f,
                       sigma=sigma, sigma_f=sigma_f, f_star=f_star),
        x0=jnp.asarray(x0), x_star=jnp.asarray(x_star),
    )


def perturbed_spec(
    key,
    base,
    *,
    dim: int,
    num_clients: int = 8,
    mu: float = 0.0,
    beta: float = 1.0,
    zeta: float = 0.0,
    sigma: float = 0.0,
    sigma_f: float = 0.0,
    f_star: Optional[float] = None,
    x_star=None,
    init_scale: float = 3.0,
    name: str = "perturbed",
) -> ProblemSpec:
    """F_i(x) = base(x) + ζ⟨u_i, x⟩ with Σu_i = 0, as a spec.

    ``base`` is a registered base id (str) or a plain function (auto-
    registered — see ``base_id_for``). The global objective is exactly the
    base, so general-convex and PL federated problems get exact ζ.
    """
    base_id = base_id_for(base)
    k_u, k_x0 = jax.random.split(key)
    u = _spread_directions(k_u, num_clients, dim)

    x0_dir = jax.random.normal(k_x0, (dim,))
    x0 = init_scale * x0_dir / jnp.linalg.norm(x0_dir)
    if x_star is not None:
        x0 = x_star + x0

    return ProblemSpec(
        family=FAMILY_PERTURBED, num_clients=num_clients, dim=dim,
        base_id=base_id, f_star_known=f_star is not None,
        x_star_known=x_star is not None, name=name,
        data=dict(u=jnp.asarray(u)),
        consts=_consts(mu=mu, beta=beta, zeta=zeta, sigma=sigma,
                       sigma_f=sigma_f, f_star=f_star),
        x0=jnp.asarray(x0),
        x_star=(jnp.asarray(x_star) if x_star is not None
                else jnp.zeros((dim,), jnp.float32)),
    )


def general_convex_spec(key, **kw):
    """Smooth general-convex base: log-cosh (1-smooth, not strongly convex)."""
    dim = kw.pop("dim", 16)
    name = kw.pop("name", "general_convex")
    return perturbed_spec(
        key, "logcosh", dim=dim, mu=0.0, beta=1.0, f_star=0.0,
        x_star=jnp.zeros((dim,)), name=name, **kw)


def pl_spec(key, **kw):
    """Nonconvex μ-PL base: f(t) = t² + 3 sin²(t); μ = 1/32, β = 8."""
    dim = kw.pop("dim", 8)
    name = kw.pop("name", "pl")
    return perturbed_spec(
        key, "pl_sin2", dim=dim, mu=1.0 / 32.0, beta=8.0, f_star=0.0,
        x_star=jnp.zeros((dim,)), name=name, **kw)


def solve_logreg_optimum(features, labels, l2: float, *, iters: int = 100,
                         tol: float = 1e-12):
    """(x*, F*) of the federated L2-logistic objective by float64 Newton.

    The per-client shards have equal sizes ([N, n, d]), so the client-mean of
    sample-means equals the mean over all pooled samples; Newton on the
    pooled objective with the exact Hessian converges to ~machine-ε in a
    handful of steps — the "high-precision" F* Table 2 needs for true
    suboptimality reporting.
    """
    X = np.asarray(features, np.float64)
    y = np.asarray(labels, np.float64)
    n_clients, n_per, d = X.shape
    Xf = X.reshape(-1, d)
    yf = y.reshape(-1)
    m = float(len(yf))
    w = np.zeros(d)
    for _ in range(iters):
        z = Xf @ w
        p = 0.5 * (1.0 + np.tanh(0.5 * z))  # overflow-stable sigmoid
        g = Xf.T @ (p - yf) / m + l2 * w
        if float(np.linalg.norm(g)) < tol:
            break
        h = (Xf * (p * (1.0 - p))[:, None]).T @ Xf / m + l2 * np.eye(d)
        w = w - np.linalg.solve(h, g)
    z = Xf @ w
    per = np.maximum(z, 0.0) - z * yf + np.log1p(np.exp(-np.abs(z)))
    f_star = float(per.mean() + 0.5 * l2 * float(w @ w))
    return w, f_star


def logreg_spec(
    key,
    *,
    features,  # [N_clients, n_i, d] per-client design matrices
    labels,  # [N_clients, n_i] in {0,1}
    l2: float = 0.1,
    oracle_batch_frac: float = 0.01,
    sigma_f: float = 0.0,
    estimate_zeta: bool = False,
    zeta_probes: int = 8,
    zeta_probe_radius: float = 1.0,
    solve_f_star: bool = True,
    name: str = "logreg",
) -> ProblemSpec:
    """Federated L2-regularized logistic regression, as a spec.

    One oracle call = one minibatch of ``oracle_batch_frac`` of the client's
    local data. ``solve_f_star`` (default) populates F*/x* by the float64
    Newton solve — Table 2 then reports TRUE suboptimality instead of raw
    loss. ``estimate_zeta`` measures ζ/ζ_F via ``core.heterogeneity`` probes
    around the init point (``key`` seeds the probes).
    """
    features = jnp.asarray(features)
    labels = jnp.asarray(labels, features.dtype)
    num_clients, n_per, dim = features.shape
    batch = max(1, int(round(oracle_batch_frac * n_per)))
    # β of logreg ≤ 0.25·max||x||² + l2 ; report a sound bound
    beta = float(0.25 * jnp.max(jnp.sum(features**2, axis=-1)) + l2)

    if solve_f_star:
        x_star, f_star = solve_logreg_optimum(features, labels, l2)
        x_star = jnp.asarray(x_star, features.dtype)
    else:
        x_star, f_star = jnp.zeros((dim,), features.dtype), None

    spec = ProblemSpec(
        family=FAMILY_LOGREG, num_clients=num_clients, dim=dim, batch=batch,
        f_star_known=f_star is not None, x_star_known=f_star is not None,
        name=name,
        data=dict(features=features, labels=labels),
        consts=_consts(mu=l2, beta=beta, sigma_f=sigma_f, f_star=f_star),
        x0=jnp.zeros((dim,), features.dtype),  # paper initializes at 0
        x_star=x_star,
    )
    if estimate_zeta:
        from repro.core import heterogeneity

        spec = heterogeneity.with_measured_heterogeneity(
            spec, key, probes=zeta_probes, radius=zeta_probe_radius)
    return spec
