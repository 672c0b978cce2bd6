"""Chip smoke test: the sweep engine end to end on a TPU.

    python chip_smoke.py [--seed N]              # one chip
    python chip_smoke.py --chips 4 [--seed N]    # four chips

One chip runs two phases through ``repro.core.sweep.run(SweepRequest(...))``
on a population shaped after the MNIST split of McMahan et al. 2017
(arXiv:1602.05629): 100 clients × 600 samples of 28×28 images, two label
shards per client. The images are synthetic class prototypes plus noise,
generated from ``--seed``.

* flat-vector: L2 logistic regression (even/odd digits, d = 784), chained
  FedAvg→SGD. One grid uses QSGD-4 with error feedback on the uplink and
  QSGD-4 on the downlink, at participation 0.1. One grid uses identity
  compression at the same participation. Together they drive
  ``aggregate_apply``, ``qsgd_dequantize``, ``chain_aggregate`` and
  ``weighted_mean_over_clients``.
* pytree: a 784-200-10 MLP (the 2NN width of the same paper, one hidden
  layer), chained FedAvg→SGD, QSGD-8 on both legs without error feedback,
  and the UCB selection policy at participation 0.1. (QSGD-4 on the
  156,800-weight layer inflates the update variance by about √d/15 ≈ 26×
  and diverges at every stepsize that trains.) This drives
  ``weighted_mean_over_clients`` and ``qsgd_dequantize`` leaf by leaf.

``--chips 4`` runs only the flat-vector QSGD grid, sharded over four chips
and vmapped on one, and compares the two. It also runs one client-axis SGD
round (rows sharded over four chips, one psum) against the single-device
round.

Each phase prints compile seconds (cold − warm wall), warm wall seconds,
cell-rounds per second, first and last suboptimality, and max
|kernel − reference| at its shapes. The reference runs at ``highest``
matmul precision. Each phase also checks that its compiled executor holds a
Mosaic ``tpu_custom_call`` for every kernel it drives, and that the warm
call re-traced nothing. The last line of stdout is one JSON object naming
the device. Exits non-zero, and prints no result, without a TPU or when any
check fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.comm import CommPlan, Leg  # noqa: E402
from repro.core import algorithms as A, chain, runner, sweep  # noqa: E402
from repro.core.algorithms import base  # noqa: E402
from repro.data import partition, spec as spec_lib, synthetic_vision  # noqa: E402
from repro.data.vision_problem import (  # noqa: E402
    vision_accuracy, vision_spec_from_shards)
from repro.kernels.aggregate import ops as agg_ops, ref as agg_ref  # noqa: E402
from repro.kernels.compress import ops as comp_ops, ref as comp_ref  # noqa: E402
from repro.selection import SelectionPolicy  # noqa: E402

EPS = float(np.finfo(np.float32).eps)


@dataclasses.dataclass(frozen=True)
class Sizes:
    num_clients: int = 100  # McMahan et al. 2017 MNIST split
    per_client: int = 600
    side: int = 28
    classes: int = 10
    hidden: int = 200  # 2NN width, one hidden layer
    rounds: int = 50
    seeds: tuple = (0, 1, 2)
    etas: tuple = (0.5, 1.0)  # chain stepsize multipliers
    participation: float = 0.1


# -- population and problems ------------------------------------------------

def population(sz: Sizes, seed: int):
    """Synthetic MNIST-shaped images dealt as two label shards per client:
    features [N, n, side²] (pixels centred on their population mean),
    labels [N, n] (int)."""
    per_class = sz.num_clients * sz.per_client // sz.classes
    images = synthetic_vision.make_prototype_images(
        num_classes=sz.classes, per_class=per_class, side=sz.side, seed=seed)
    pixels = images.reshape(sz.classes * per_class, -1)
    labels = np.repeat(np.arange(sz.classes), per_class)
    return partition.pathological_shards(
        pixels - pixels.mean(axis=0), labels,
        num_clients=sz.num_clients, shards_per_client=2, seed=seed)


LOGREG_L2 = 1e-3
LOGREG_ETA = 0.2
LOGREG_K = 16


def logreg_problem(cx, cy, seed: int, *, solve_f_star: bool = True):
    return spec_lib.logreg_spec(
        jax.random.PRNGKey(seed), features=cx,
        labels=synthetic_vision.binary_labels_even_odd(cy), l2=LOGREG_L2,
        oracle_batch_frac=0.01, solve_f_star=solve_f_star)


def logreg_chain():
    return chain.fedchain(
        A.FedAvg.from_k(LOGREG_K, eta=LOGREG_ETA),
        A.SGD(eta=LOGREG_ETA, k=LOGREG_K, mu_avg=LOGREG_L2),
        selection_k=LOGREG_K, name="fedavg->sgd")


def mlp_problem(cx, cy, sz: Sizes, seed: int):
    return vision_spec_from_shards(
        jax.random.PRNGKey(seed), cx, cy, num_classes=sz.classes,
        hidden=sz.hidden, batch=10, l2=1e-4)


def mlp_chain():
    return chain.fedchain(
        A.FedAvg(eta=0.1, local_steps=5, inner_batch=1),
        A.SGD(eta=0.1, k=5, output_mode="last"),
        selection_k=5, name="fedavg->sgd")


QSGD4 = Leg("qsgd", qsgd_bits=4)
QSGD4_EF = Leg("qsgd", qsgd_bits=4, error_feedback=True)
QSGD8 = Leg("qsgd", qsgd_bits=8)


# -- measurement helpers ----------------------------------------------------

def _arrays(res):
    return [leaf for f in dataclasses.fields(res)
            for leaf in jax.tree.leaves(getattr(res, f.name))
            if isinstance(leaf, jax.Array)]


def _abstract(tree):
    return jax.tree.map(
        lambda a: (jax.ShapeDtypeStruct(a.shape, a.dtype)
                   if isinstance(a, jax.Array) else a), tree)


def timed_grid(req):
    """Run ``req`` cold, then warm under the no-retrace contract. Returns
    (result, cold_s, warm_s, executor HLO texts)."""
    runner.AUDIT_SINK = []
    try:
        t0 = time.perf_counter()
        jax.block_until_ready(_arrays(sweep.run(req)))
        cold = time.perf_counter() - t0
        calls = runner.AUDIT_SINK
    finally:
        runner.AUDIT_SINK = None
    with runner.assert_no_retrace(what="warm grid call"):
        t0 = time.perf_counter()
        res = sweep.run(req)
        jax.block_until_ready(_arrays(res))
        warm = time.perf_counter() - t0
    # the executors' compiled programs, from the recorded operand shapes
    # (donated operands are gone; their avals are all lowering needs)
    with runner.assert_no_retrace(what="executor HLO lowering"):
        hlo = [fn.lower(*_abstract(args), **_abstract(kw)).compile().as_text()
               for _, fn, args, kw in calls]
    return res, cold, warm, hlo


def mosaic_kernels(hlo_texts):
    """Names of the Pallas kernels compiled as Mosaic custom calls."""
    found = set()
    for text in hlo_texts:
        for line in text.splitlines():
            if 'custom_call_target="tpu_custom_call"' in line:
                m = re.match(r"\s*(?:ROOT\s+)?%?([A-Za-z_]+)", line)
                if m:
                    found.add(m.group(1))
    return found


def check(cond: bool, what: str):
    if not cond:
        raise AssertionError(what)


def check_history(name, hist):
    hist = np.asarray(hist)
    check(np.isfinite(hist).all(), f"{name}: non-finite history")
    check((hist[..., -1] < hist[..., 0]).all(),
          f"{name}: a cell's history did not fall below its start: first "
          f"{hist[..., 0].ravel()}, last {hist[..., -1].ravel()}")


def kernel_errors(s: int, d: int, key):
    """{kernel: (max |kernel − ref|, max of |kernel − ref| / bound)} for the
    four sweep-path kernels on random [S, D] rows. Each bound is the f32
    error of the reference's own expression: a sum of S terms in any order
    is within (S+2)·ε·Σ|terms| of the exact sum (Higham, Accuracy and
    Stability, §4.2), so two orders are within twice that. QSGD is
    elementwise: outside a level flip the outputs agree to 8ε of the row
    norm; a one-ulp difference in |v|/‖v‖·L at a stochastic-rounding
    threshold moves one element by one level, which happens at a rate of
    about 2⁻²³ per element, so flips are allowed at up to 10⁻⁵ of the
    elements. QSGD errors are relative to the row norm."""
    ks = jax.random.split(key, 8)
    x = jax.random.normal(ks[0], (d,))
    g = jax.random.normal(ks[1], (s, d))
    ci = jax.random.normal(ks[2], (s, d))
    c = jax.random.normal(ks[3], (d,))
    res = jax.random.normal(ks[4], (s, d))
    m = (jax.random.uniform(ks[5], (s,)) < 0.5).astype(jnp.float32)
    w = jax.random.uniform(ks[6], (s,)) / s
    u = jax.random.uniform(ks[7], (s, d))
    aw = jnp.abs(w)[:, None]
    gamma = 2 * (s + 2) * EPS
    out = {}
    with jax.default_matmul_precision("highest"):
        want = agg_ref.chain_aggregate_ref(x, g, ci, c, lr=1.0, weights=w)
        bound = gamma * (jnp.abs(x) + jnp.abs(c)
                         + jnp.sum(aw * (jnp.abs(g) + jnp.abs(ci)), 0))
        got = agg_ops.chain_aggregate(x, g, ci, c, w, lr=1.0)
        out["chain_aggregate"] = _err(got, want, bound)

        # wire rows g, compressed rows ci, EF input res + g, residuals res
        want_x, want_r = agg_ref.aggregate_apply_ref(x, g, ci, res + g, res,
                                                     m, w)
        got_x, got_r = agg_ops.aggregate_apply(x, g, ci, res + g, res, m, w)
        bound = gamma * (jnp.abs(x) + jnp.sum(aw * jnp.abs(g), 0))
        r_bound = 4 * EPS * (jnp.abs(res + g) + jnp.abs(ci) + jnp.abs(res))
        out["aggregate_apply"] = tuple(map(max, _err(got_x, want_x, bound),
                                           _err(got_r, want_r, r_bound)))

        want = comp_ref.weighted_mean_over_clients_ref(g, w)
        got = comp_ops.weighted_mean_over_clients(g, w)
        bound = gamma * jnp.sum(aw * jnp.abs(g), 0) / s + EPS * jnp.abs(want)
        out["weighted_mean_over_clients"] = _err(got, want, bound)

        norms = jnp.linalg.norm(g, axis=1)
        levels = jnp.float32(15.0)  # 4 bits
        want = comp_ref.qsgd_dequantize_ref(g, u, norms, levels)
        got = comp_ops.qsgd_dequantize(g, u, norms, levels)
        diff = jnp.abs(got - want) / norms[:, None]
        flips = diff > 0.5 / 15.0
        flip_rate = float(jnp.mean(flips))
        check(flip_rate <= 1e-5, f"qsgd_dequantize: level flips at rate "
              f"{flip_rate:.2e} > 1e-5 at [{s}, {d}]")
        close = float(jnp.max(jnp.where(flips, 0.0, diff)))
        out["qsgd_dequantize"] = (close, close / (8 * EPS))
    for name, (_, ratio) in out.items():
        check(ratio <= 1.0, f"{name}: |kernel − ref| is {ratio:.3g}× its "
              f"bound at [{s}, {d}]")
    return out


def _err(got, want, bound):
    diff = jnp.abs(got - want)
    return float(jnp.max(diff)), float(jnp.max(diff / bound))


def report_kernels(phase: str, errs: dict, **fields):
    for name, (err, ratio) in errs.items():
        fields[f"{name}_max_err"] = err
        fields[f"{name}_err_over_bound"] = ratio
    report(phase, **fields)


def report(phase: str, **fields):
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def grid_report(phase, res, cold, warm, hlo, kernels, n_cells):
    hist = np.asarray(res.history)
    found = mosaic_kernels(hlo)
    report(phase, compile_s=cold - warm, warm_s=warm,
           cell_rounds_per_s=n_cells * hist.shape[-1] / warm,
           first_sub=float(np.median(hist[..., 0])),
           last_sub=float(np.median(hist[..., -1])),
           best_last_sub=float(np.min(hist[..., -1])),
           mosaic_kernels=",".join(sorted(found)))
    check_history(phase, hist)
    check(set(kernels) <= found,
          f"{phase}: executor lacks Mosaic kernels "
          f"{sorted(set(kernels) - found)} (found {sorted(found)})")


# -- phases -----------------------------------------------------------------

def flat_phase(sz: Sizes, cx, cy, seed: int):
    spec = logreg_problem(cx, cy, seed)
    method = logreg_chain()
    n_cells = len(sz.seeds) * len(sz.etas)
    for tag, plan, kernels in (
            ("flat/qsgd4-ef", CommPlan(uplink=QSGD4_EF, downlink=QSGD4,
                                       participation=sz.participation),
             ("aggregate_apply", "qsgd_dequantize")),
            ("flat/identity", CommPlan(participation=sz.participation),
             ("chain_aggregate", "weighted_mean_over_clients"))):
        req = sweep.SweepRequest(
            algo_or_chain=method, problem=spec, x0=spec.x0,
            rounds=sz.rounds, seeds=sz.seeds, etas=sz.etas, comm=plan)
        grid_report(tag, *timed_grid(req), kernels, n_cells)
    errs = kernel_errors(sz.num_clients, spec.dim, jax.random.PRNGKey(seed))
    report_kernels("flat/kernels", errs, rows=f"[{sz.num_clients},{spec.dim}]")


def mlp_phase(sz: Sizes, cx, cy, seed: int):
    spec = mlp_problem(cx, cy, sz, seed)
    req = sweep.SweepRequest(
        algo_or_chain=mlp_chain(), problem=spec, x0=spec.x0,
        rounds=sz.rounds, seeds=sz.seeds, etas=sz.etas,
        comm=CommPlan(uplink=QSGD8, downlink=QSGD8),
        policies=(SelectionPolicy("ucb", participation=sz.participation),))
    res, cold, warm, hlo = timed_grid(req)
    grid_report("mlp/ucb-qsgd8", res, cold, warm, hlo,
                ("weighted_mean_over_clients", "qsgd_dequantize"),
                len(sz.seeds) * len(sz.etas))
    acc = vision_accuracy(spec)
    best = np.unravel_index(int(np.argmin(np.asarray(res.final_sub))),
                            np.shape(res.final_sub))
    acc0 = float(acc(spec.x0))
    acc1 = float(acc(jax.tree.map(lambda l: l[best], res.x_hat)))
    check(acc1 > acc0, f"mlp: accuracy fell from {acc0} to {acc1}")
    report("mlp/accuracy", init=acc0, best_cell=acc1)
    for name, leaf in spec.x0.items():
        errs = kernel_errors(sz.num_clients, leaf.size,
                             jax.random.PRNGKey(seed + leaf.size))
        report_kernels("mlp/kernels", errs, leaf=name,
                       rows=f"[{sz.num_clients},{leaf.size}]")


def four_chip_phase(sz: Sizes, cx, cy, seed: int):
    """The flat QSGD grid sharded over four chips vs vmapped on one, and a
    client-axis SGD round vs the single-device round."""
    from repro.dist import client_axis, make_grid_client_mesh, make_grid_mesh

    check(len(jax.devices()) == 4, f"--chips 4 needs four devices, found "
          f"{len(jax.devices())}")
    spec = logreg_problem(cx, cy, seed, solve_f_star=False)
    seeds = tuple(range(4))
    req = sweep.SweepRequest(
        algo_or_chain=logreg_chain(), problem=spec, x0=spec.x0,
        rounds=sz.rounds, seeds=seeds, etas=sz.etas,
        comm=CommPlan(uplink=QSGD4_EF, downlink=QSGD4,
                      participation=sz.participation))
    n_cells = len(seeds) * len(sz.etas)
    one = timed_grid(req)
    four = timed_grid(dataclasses.replace(req, mesh=make_grid_mesh(4)))
    for tag, (res, cold, warm, hlo) in (("4chip/vmapped-1", one),
                                        ("4chip/sharded-4", four)):
        grid_report(tag, res, cold, warm, hlo,
                    ("aggregate_apply", "qsgd_dequantize"), n_cells)
    # dist.grid: the bits ledgers are closed-form counts and stay exact; the
    # float results of separately compiled shard programs agree to a
    # tolerance. Each round's loss is an f32 mean over 60,000 samples whose
    # reduction order XLA picks per program (~1e-6 relative), and a one-ulp
    # difference in a client's delta can flip one QSGD stochastic rounding,
    # moving one coordinate by one level (≈ η‖g‖/(15·S)) and the loss by
    # ≲ 1e-4 of its start. So |Δhistory| ≤ 1e-3 · history at round one,
    # while a wrong shard or a missing cell moves it by O(1).
    a, b = one[0], four[0]
    fields = ("history", "final_sub", "bits_up", "bits_down", "x_hat")
    unequal = [f for f in fields
               if not all(np.array_equal(np.asarray(p), np.asarray(q))
                          for p, q in zip(jax.tree.leaves(getattr(a, f)),
                                          jax.tree.leaves(getattr(b, f))))]
    ha, hb = np.asarray(a.history), np.asarray(b.history)
    gap = float(np.max(np.abs(ha - hb)))
    gap_ratio = float(np.max(np.abs(ha - hb) / (1e-3 * ha[..., :1])))
    report("4chip/grid-compare", bitwise=not unequal,
           unequal_fields=",".join(unequal) or "-", max_history_diff=gap,
           diff_over_tolerance=gap_ratio)

    # client axis: rows sharded over four chips, one psum join. The sum
    # over N clients is reassociated into four partial sums, and each
    # shard vmaps its own oracle calls, so the two rounds agree within the
    # f32 bound of an N-term sum of the weighted gradients plus the d-term
    # dot products inside each gradient.
    mesh = make_grid_client_mesh(1, 4)
    algo = A.SGD(eta=LOGREG_ETA, k=LOGREG_K)
    key = jax.random.PRNGKey(seed)
    x0 = spec.x0 + 0.01 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                            spec.x0.shape)
    single = jax.jit(lambda sp, x, k: algo.round(sp, algo.init(sp, x), k).x)
    sharded = jax.jit(lambda sp, x, k: client_axis.sgd_round_client_sharded(
        mesh, sp, x, LOGREG_ETA, k, k=LOGREG_K))

    def grads(sp, x, k):
        k_sample, k_grad = jax.random.split(k)
        cids = base.sample_clients(k_sample, sp.num_clients, sp.num_clients)
        return base.grad_k(sp, x, cids, k_grad, LOGREG_K)

    t0 = time.perf_counter()
    want = jax.block_until_ready(single(spec, x0, key))
    t1 = time.perf_counter()
    got = jax.block_until_ready(sharded(spec, x0, key))
    t2 = time.perf_counter()
    g = jax.jit(grads)(spec, x0, key)
    n = spec.num_clients
    bound = 8 * (n + spec.dim) * EPS * (
        jnp.abs(x0) + LOGREG_ETA / n * jnp.sum(jnp.abs(g), 0))
    ratio = float(jnp.max(jnp.abs(got - want) / bound))
    report("4chip/client-axis", single_cold_s=t1 - t0,
           sharded_cold_s=t2 - t1,
           max_abs_diff=float(jnp.max(jnp.abs(got - want))),
           diff_over_bound=ratio,
           bitwise=bool(jnp.array_equal(got, want)))
    check(not {"bits_up", "bits_down"} & set(unequal),
          "sharded grid's bits ledgers differ from the vmapped grid's")
    check(gap_ratio <= 1.0, f"sharded grid's history is {gap_ratio:.3g}× its "
          f"tolerance off the vmapped grid's (max diff {gap})")
    check(ratio <= 1.0, f"client-axis round is {ratio:.3g}× its bound off "
          f"the single-device round")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    cache = compile_cache.enable()
    warm_cache = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"compile cache {cache}: {warm_cache} entries at start", flush=True)
    sz = Sizes()
    t0 = time.perf_counter()
    cx, cy = population(sz, args.seed)
    report("population", clients=cx.shape[0], per_client=cx.shape[1],
           dim=cx.shape[2], setup_s=time.perf_counter() - t0)
    if args.chips == 4:
        four_chip_phase(sz, cx, cy, args.seed)
    else:
        flat_phase(sz, cx, cy, args.seed)
        mlp_phase(sz, cx, cy, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
