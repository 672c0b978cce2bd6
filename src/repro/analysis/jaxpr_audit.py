"""Layer 2: jaxpr const-capture audit of the cached executor stack.

The lint (Layer 1) argues operand discipline from source; this module
PROVES it dynamically. It runs tiny spec-backed workloads through every
cached executor family — runner / chain / sweep (indexed layout) /
selection, on BOTH the vmapped and sharded engines, plus the
telemetry-enabled (``repro.obs.Telemetry``) sweep variants — with
``runner.AUDIT_SINK`` armed, so each top-level executor call records
``(cache_key, fn, args)``. Each recorded executor is then re-traced on its
REAL operands with ``jax.make_jaxpr`` and the ``ClosedJaxpr`` consts are
walked recursively (pjit / scan / cond sub-jaxprs included). An executor
whose operands all arrived as arguments closes over (almost) nothing; any
family whose total array-const bytes exceed
``repro.analysis.CONST_BYTE_CEILING`` fails the audit — that is exactly a
data shard, key stack, or schedule baked in by closure.

The audit must run on a host backend (CPU / interpret): donation is a
no-op there, so the recorded argument arrays stay valid for the re-trace.

``run_audit(only=...)`` restricts to named workloads (the unit test runs
just the indexed sweep; CI and ``benchmarks/analysis_audit.py`` run all).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.extend import core as jax_core

from repro.analysis import CONST_BYTE_CEILING
from repro.core import runner


def _tiny_context():
    """One tiny spec-backed problem family + methods, shared by all
    workloads (4 clients × dim 8 × 4 rounds keeps every compile cheap)."""
    from repro.comm import CommConfig
    from repro.core import algorithms as A, chain as chain_lib
    from repro.data import spec as spec_lib

    spec = spec_lib.quadratic_spec(
        jax.random.PRNGKey(0), num_clients=4, dim=8, mu=0.1, beta=1.0,
        zeta=1.0, sigma=0.1)
    spec2 = spec_lib.quadratic_spec(
        jax.random.PRNGKey(1), num_clients=4, dim=8, mu=0.1, beta=1.0,
        zeta=2.0, sigma=0.2)
    algo = A.SGD(eta=0.4, k=4, mu_avg=0.1)
    ch = chain_lib.fedchain(
        A.FedAvg(eta=0.3, local_steps=2, inner_batch=2),
        A.SGD(eta=0.4, k=4, mu_avg=0.1))
    comm = CommConfig(compressor="qsgd", qsgd_bits=4, participation=0.5)
    return spec, spec2, algo, ch, comm


ROUNDS = 4
_SEEDS = (0, 1)
_ETAS = (0.5, 1.0)


def _workloads() -> Dict[str, callable]:
    """name → thunk exercising one executor family on tiny operands."""
    spec, spec2, algo, ch, comm = _tiny_context()
    from repro.core import sweep
    from repro.obs import Telemetry
    from repro.selection import SelectionPolicy, run_selection_sweep

    key = jax.random.PRNGKey(7)
    pols = (SelectionPolicy("uniform", participation=0.5),
            SelectionPolicy("ucb", participation=0.5, ucb_c=0.5))
    tel = Telemetry(grad_norm=True)  # every tap channel on

    def _mesh():
        from repro.dist import make_grid_mesh

        return make_grid_mesh(1)

    return {
        "runner": lambda: runner.run(algo, spec, spec.x0, ROUNDS, key),
        "runner-comm": lambda: runner.run(algo, spec, spec.x0, ROUNDS, key,
                                          comm=comm),
        "chain": lambda: ch.run(spec, spec.x0, ROUNDS, key),
        "chain-comm": lambda: ch.run(spec, spec.x0, ROUNDS, key, comm=comm),
        "sweep": lambda: sweep.run_sweep(
            algo, None, None, ROUNDS, seeds=_SEEDS, etas=_ETAS,
            problems=[spec, spec2]),
        "sweep-comm": lambda: sweep.run_sweep(
            algo, None, None, ROUNDS, seeds=_SEEDS, etas=_ETAS,
            problems=[spec, spec2], comm=comm),
        "sweep-chain": lambda: sweep.run_sweep(
            ch, None, None, ROUNDS, seeds=_SEEDS, etas=_ETAS,
            problems=[spec, spec2]),
        "sweep-chain-comm": lambda: sweep.run_sweep(
            ch, None, None, ROUNDS, seeds=_SEEDS, etas=_ETAS,
            problems=[spec, spec2], comm=comm),
        "fraction": lambda: sweep.run_fraction_sweep(
            ch, spec, spec.x0, ROUNDS, seeds=_SEEDS, fractions=(0.3, 0.6)),
        "decay": lambda: sweep.run_decay_sweep(
            ch, spec, spec.x0, ROUNDS, seeds=_SEEDS, decay_factors=(0.5,)),
        "methods": lambda: sweep.run_method_sweep(
            (type(algo)(eta=0.4, k=4, mu_avg=0.1),
             type(algo)(eta=0.4, k=4, mu_avg=0.2)),
            spec, spec.x0, ROUNDS, seeds=_SEEDS),
        "selection": lambda: run_selection_sweep(
            algo, None, None, ROUNDS, policies=pols, problems=[spec],
            seeds=_SEEDS, etas=(1.0,)),
        "selection-chain": lambda: run_selection_sweep(
            ch, None, None, ROUNDS, policies=pols, problems=[spec],
            seeds=_SEEDS, etas=(1.0,)),
        "dist": lambda: sweep.run_sweep(
            algo, None, None, ROUNDS, seeds=_SEEDS, etas=_ETAS,
            problems=[spec, spec2], mesh=_mesh()),
        "dist-chain-comm": lambda: sweep.run_sweep(
            ch, None, None, ROUNDS, seeds=_SEEDS, etas=_ETAS,
            problems=[spec, spec2], comm=comm, mesh=_mesh()),
        "dist-fraction": lambda: sweep.run_fraction_sweep(
            ch, spec, spec.x0, ROUNDS, seeds=_SEEDS, fractions=(0.3, 0.6),
            mesh=_mesh()),
        "dist-selection": lambda: run_selection_sweep(
            ch, None, None, ROUNDS, policies=pols, problems=[spec],
            seeds=_SEEDS, etas=(1.0,), mesh=_mesh()),
        # telemetry-enabled variants: the round taps ride the scan as extra
        # outputs and MUST NOT smuggle operands in as consts either
        "sweep-telemetry": lambda: sweep.run_sweep(
            algo, None, None, ROUNDS, seeds=_SEEDS, etas=_ETAS,
            problems=[spec, spec2], comm=comm, telemetry=tel),
        "sweep-chain-telemetry": lambda: sweep.run_sweep(
            ch, None, None, ROUNDS, seeds=_SEEDS, etas=_ETAS,
            problems=[spec, spec2], comm=comm, telemetry=tel),
        "selection-telemetry": lambda: run_selection_sweep(
            algo, None, None, ROUNDS, policies=pols, problems=[spec],
            seeds=_SEEDS, etas=(1.0,), telemetry=tel),
        "dist-telemetry": lambda: sweep.run_sweep(
            algo, None, None, ROUNDS, seeds=_SEEDS, etas=_ETAS,
            problems=[spec, spec2], comm=comm, mesh=_mesh(), telemetry=tel),
    }


def collect_executor_records(only: Optional[Sequence[str]] = None
                             ) -> Dict[str, list]:
    """Run the workloads with the audit sink armed; returns
    workload-name → [(cache_key, fn, args, kwargs), ...] with one record
    per distinct cache key (the first top-level call of each executor)."""
    workloads = _workloads()
    unknown = set(only or ()) - set(workloads)
    if unknown:
        raise ValueError(f"unknown audit workload(s): {sorted(unknown)}; "
                         f"valid: {sorted(workloads)}")
    out: Dict[str, list] = {}
    runner.clear_executor_cache()
    for name, thunk in workloads.items():
        if only is not None and name not in only:
            continue
        sink: list = []
        runner.AUDIT_SINK = sink
        try:
            thunk()
        finally:
            runner.AUDIT_SINK = None
        seen_keys = set()
        records = []
        for key, fn, args, kwargs in sink:
            kid = id(fn)
            if kid not in seen_keys:
                seen_keys.add(kid)
                records.append((key, fn, args, kwargs))
        out[name] = records
    return out


def _sub_jaxprs(value):
    if isinstance(value, jax_core.ClosedJaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _sub_jaxprs(v)


def collect_consts(closed_jaxpr) -> List[object]:
    """Every array const reachable from the jaxpr, including inside pjit /
    scan / cond sub-jaxprs, deduplicated by object identity."""
    seen, out = set(), []

    def walk(cj):
        if id(cj) in seen:
            return
        seen.add(id(cj))
        for c in cj.consts:
            if hasattr(c, "shape") and hasattr(c, "dtype") \
                    and id(c) not in seen:
                seen.add(id(c))
                out.append(c)
        for eqn in cj.jaxpr.eqns:
            for v in eqn.params.values():
                for sub in _sub_jaxprs(v):
                    walk(sub)

    walk(closed_jaxpr)
    return out


def _const_bytes(c) -> int:
    try:
        return int(c.size) * int(jnp.dtype(c.dtype).itemsize)
    except (TypeError, ValueError):
        return 0


def audit_record(fn, args, kwargs) -> dict:
    """Re-trace one executor on its recorded operands; summarize consts."""
    closed = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    consts = collect_consts(closed)
    sizes = sorted((_const_bytes(c) for c in consts), reverse=True)
    return {
        "n_consts": len(consts),
        "const_bytes": int(sum(sizes)),
        "max_const_bytes": int(sizes[0]) if sizes else 0,
    }


def run_audit(only: Optional[Sequence[str]] = None,
              ceiling: int = CONST_BYTE_CEILING
              ) -> Tuple[dict, List[str]]:
    """(report, failures). ``report['families']`` maps each audited
    executor family to its const summary; a family fails when its TOTAL
    array-const bytes exceed ``ceiling``."""
    records = collect_executor_records(only=only)
    families: Dict[str, dict] = {}
    failures: List[str] = []
    for workload, recs in records.items():
        if not recs:
            failures.append(
                f"{workload}: no executor call recorded — the audit sink "
                f"saw nothing (workload bypassed the executor cache?)")
            continue
        for i, (key, fn, args, kwargs) in enumerate(recs):
            name = f"{workload}/{key[0]}" if isinstance(
                key, tuple) and key else workload
            if name in families:
                name = f"{name}#{i}"
            summary = audit_record(fn, args, kwargs)
            families[name] = summary
            if summary["const_bytes"] > ceiling:
                failures.append(
                    f"{name}: {summary['const_bytes']} bytes of array "
                    f"consts baked into the traced executor (ceiling "
                    f"{ceiling}) — an operand is being captured by closure")
    report = {
        "const_ceiling_bytes": int(ceiling),
        "rounds": ROUNDS,
        "families": families,
        "total_const_bytes": int(sum(
            f["const_bytes"] for f in families.values())),
    }
    return report, failures
