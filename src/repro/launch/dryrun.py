import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture × input shape) on the production meshes and extract the
memory / cost / collective analysis that §Roofline reads.

The two lines above MUST stay first: jax locks the device count on first init.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-4b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all --mesh both
  ... --fedchain            # additionally dry-run the FedChain local/sync steps
Results: experiments/dryrun/<arch>__<shape>__<mesh>.json
"""
import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import INPUT_SHAPES, registry  # noqa: E402
from repro.launch import analysis  # noqa: E402
from repro.launch.mesh import (  # noqa: E402
    PRODUCTION_DEVICE_KIND, data_shards, make_production_mesh)
from repro.models import model_zoo, transformer  # noqa: E402
from repro.optim import sgd  # noqa: E402
from repro.sharding import RuleSet, param_specs, use_rules  # noqa: E402
from repro.sharding.rules import cache_specs_tree  # noqa: E402

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun")


def _depth_units(cfg):
    """Depth units for FLOP/collective extrapolation.

    XLA's cost_analysis counts a scan (while-loop) body ONCE, ignoring the
    trip count, so the scanned compile undercounts FLOPs/collective bytes by
    ~num_layers×. Per-layer costs are additive in depth, so we compile tiny
    *unrolled* variants (every unit at 1, then each unit at 2) and solve
    total = a + Σ_u b_u·count_u exactly.
    Returns {unit_name: full_count}.
    """
    units = {}
    if cfg.arch_type == "hybrid":
        # one unit = `period` mamba layers + 1 shared-attn application;
        # the tail (num_layers % period) is approximated as a fraction.
        units["group"] = cfg.num_layers / cfg.hybrid.period
        return units
    if cfg.moe is not None and cfg.moe.first_dense_layers > 0:
        units["dense"] = cfg.moe.first_dense_layers
        units["moe"] = cfg.num_layers - cfg.moe.first_dense_layers
    elif cfg.moe is not None:
        units["moe"] = cfg.num_layers
    else:
        units["decoder"] = cfg.num_layers
    if cfg.encoder is not None:
        units["encoder"] = cfg.encoder.num_layers
    return units


def _variant_cfg(cfg, counts):
    """A depth-reduced unrolled clone: each unit at counts[unit] layers."""
    import dataclasses as dc

    kw = dict(scan_layers=False)
    if cfg.arch_type == "hybrid":
        kw["num_layers"] = counts["group"] * cfg.hybrid.period
    elif cfg.moe is not None and cfg.moe.first_dense_layers > 0:
        kw["num_layers"] = counts["dense"] + counts["moe"]
        kw["moe"] = dc.replace(cfg.moe, first_dense_layers=counts["dense"])
    elif cfg.moe is not None:
        kw["num_layers"] = counts["moe"]
    else:
        kw["num_layers"] = counts["decoder"]
    if cfg.encoder is not None:
        kw["encoder"] = dc.replace(cfg.encoder, num_layers=counts["encoder"])
    return dc.replace(cfg, **kw)


def _extrapolate(base, bumps, units):
    """total = a + Σ b_u·count_u given f(1,..,1) and f(..,2_u,..)."""
    b = {u: bumped - base for u, bumped in bumps.items()}
    a = base - sum(b.values())
    return a + sum(b[u] * units[u] for u in units)


def _cost_record(compiled):
    cost = analysis.cost_dict(compiled)
    colls = analysis.parse_collectives(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "colls": colls,
    }


def _combine_colls(base, bumps, units):
    out = {}
    for kind in analysis.COLLECTIVE_OPS:
        rec = {}
        for field in ("count", "bytes"):
            rec[field] = max(0.0, _extrapolate(
                base["colls"][kind][field],
                {u: b["colls"][kind][field] for u, b in bumps.items()}, units))
        out[kind] = rec
    return out


def _skip_reason(cfg, shape) -> str:
    if shape.name == "long_500k" and not cfg.subquadratic:
        return "long_500k requires sub-quadratic attention (DESIGN.md §4 skip table)"
    return ""


def _batch_shardings(cfg, shape, rs: RuleSet):
    specs = model_zoo.batch_specs(cfg, shape)

    def spec_of(name, leaf):
        axes = ("batch",) + (None,) * (len(leaf.shape) - 1)
        return rs.spec_for(axes, leaf.shape)

    return {k: NamedSharding(rs.mesh, spec_of(k, v)) for k, v in specs.items()}


def _named(tree_specs, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda s: isinstance(s, P))


def _compile_step(cfg, shape, mesh, rs: RuleSet, groups: int):
    """Build the right step fn for the shape kind, lower and compile it."""
    param_shapes = jax.eval_shape(
        lambda k: transformer.init_model(cfg, k), jax.ShapeDtypeStruct((2,), jnp.uint32))
    p_shardings = _named(param_specs(param_shapes, rs), mesh)

    t0 = time.time()
    with use_rules(rs):
        if shape.kind == "train":
            opt = sgd(1e-2)
            step = model_zoo.make_train_step(cfg, opt, moe_groups=groups)
            b_shardings = _batch_shardings(cfg, shape, rs)
            # repro: allow[R4] one-shot AOT lowering jit, never cached
            jitted = jax.jit(
                step,
                in_shardings=(p_shardings, (), b_shardings),
                out_shardings=(p_shardings, (), None),
                donate_argnums=(0,),
            )
            args = (param_shapes, (), model_zoo.batch_specs(cfg, shape))
        elif shape.kind == "prefill":
            step = model_zoo.make_prefill_step(cfg, moe_groups=groups)
            cache_shapes = transformer.cache_specs(cfg, shape.global_batch, shape.seq_len)
            c_shardings = _named(cache_specs_tree(cache_shapes, rs), mesh)
            b_shardings = _batch_shardings(cfg, shape, rs)
            # repro: allow[R4] one-shot AOT lowering jit, never cached
            jitted = jax.jit(
                step,
                in_shardings=(p_shardings, b_shardings, c_shardings),
                out_shardings=(None, c_shardings),
                donate_argnums=(2,),
            )
            args = (param_shapes, model_zoo.batch_specs(cfg, shape), cache_shapes)
        else:  # decode
            step = model_zoo.make_serve_step(cfg, moe_groups=groups)
            specs = model_zoo.decode_specs(cfg, shape)
            c_shardings = _named(cache_specs_tree(specs["caches"], rs), mesh)
            tok_sh = NamedSharding(mesh, rs.spec_for(("batch", None), specs["tokens"].shape))
            in_sh = [p_shardings, c_shardings, tok_sh, NamedSharding(mesh, P())]
            args = [param_shapes, specs["caches"], specs["tokens"], specs["pos"]]
            if cfg.encoder is not None:
                x_sh = _named(cache_specs_tree(specs["cross_kv"], rs), mesh)
                in_sh.append(x_sh)
                args.append(specs["cross_kv"])
            # repro: allow[R4] one-shot AOT lowering jit, never cached
            jitted = jax.jit(
                step,
                in_shardings=tuple(in_sh),
                out_shardings=(None, c_shardings),
                donate_argnums=(1,),
            )
            args = tuple(args)

        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    return compiled, round(t_lower, 2), round(t_compile, 2)


def lower_one(arch: str, shape_name: str, mesh, mesh_name: str, *,
              mla_absorb: bool = False, seq_shard: bool = False,
              attn_fallback: bool = False, fsdp: bool = False,
              measure_depth: bool = True):
    """Lower + compile one (arch × shape × mesh); returns the result record.

    The full (scanned) compile proves the config lowers and gives
    memory_analysis; tiny unrolled depth variants recover trip-count-exact
    FLOPs and collective bytes (see _depth_units).
    """
    import dataclasses

    cfg = registry.get_config(arch)
    if mla_absorb and cfg.mla is not None:
        cfg = dataclasses.replace(cfg, mla=dataclasses.replace(cfg.mla, absorb_decode=True))
    shape = INPUT_SHAPES[shape_name]
    reason = _skip_reason(cfg, shape)
    if reason:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": reason}

    rules = None
    if seq_shard:
        # §Perf: shard activations' sequence axis over the model axis so the
        # remat-saved scan carries shard 256-way (keeps weight sharding).
        rules = {"seq": "model"}
    rs = RuleSet(mesh, rules, attn_embed_fallback=attn_fallback, fsdp=fsdp)
    n_chips = mesh.devices.size
    groups = data_shards(mesh)

    compiled, t_lower, t_compile = _compile_step(cfg, shape, mesh, rs, groups)
    mem = compiled.memory_analysis()
    raw = _cost_record(compiled)

    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "status": "ok",
        "n_chips": n_chips,
        "lower_s": t_lower,
        "compile_s": t_compile,
        "memory": analysis.memory_summary(mem),
        "cost_scanned_raw": {"flops": raw["flops"], "bytes": raw["bytes"]},
        "params": model_zoo.param_count(cfg),
        "active_params": model_zoo.active_param_count(cfg),
    }

    mf = model_zoo.model_flops(cfg, shape)
    if measure_depth:
        units = _depth_units(cfg)
        ones = {u: 1 for u in units}
        base_cfg = _variant_cfg(cfg, ones)
        c0, _, _ = _compile_step(base_cfg, shape, mesh, rs, groups)
        base = _cost_record(c0)
        bumps = {}
        for u in units:
            counts = dict(ones)
            counts[u] = 2
            cu, _, _ = _compile_step(_variant_cfg(cfg, counts), shape, mesh, rs, groups)
            bumps[u] = _cost_record(cu)
        flops = _extrapolate(base["flops"], {u: b["flops"] for u, b in bumps.items()}, units)
        hbytes = _extrapolate(base["bytes"], {u: b["bytes"] for u, b in bumps.items()}, units)
        colls = _combine_colls(base, bumps, units)
        rec["cost_extrapolated"] = {"flops": flops, "bytes": hbytes}
        roof = analysis.roofline({"flops": flops, "bytes accessed": hbytes}, colls,
                                 n_chips=n_chips, model_flops=mf,
                                 device_kind=PRODUCTION_DEVICE_KIND)
    else:
        roof = analysis.roofline({"flops": raw["flops"], "bytes accessed": raw["bytes"]},
                                 raw["colls"], n_chips=n_chips, model_flops=mf,
                                 device_kind=PRODUCTION_DEVICE_KIND)
        rec["note"] = "scanned-HLO cost (while-body counted once); see single-pod for exact"
    rec["roofline"] = roof.to_dict()
    return rec


def lower_fedchain(arch: str, mesh, mesh_name: str):
    """Dry-run the FedChain phases: local steps (must show zero cross-client
    collective growth), the sync step, and the global step, for §Perf."""
    import dataclasses as dc

    from repro.launch import fedchain as fc

    cfg = registry.get_config(arch)
    shape = dc.replace(INPUT_SHAPES["train_4k"])
    # FL layout: the client axis ("pod") holds per-client replicas, so the
    # activation "batch" axis must bind to "data" ONLY — otherwise the
    # logical() constraints inside the vmapped per-client step would force
    # resharding across clients (cross-pod traffic in the local phase).
    rs = RuleSet(mesh, {"batch": "data"})
    groups = data_shards(mesh)
    c_ax = fc.client_axis_name(mesh)
    n_clients = fc.num_clients(mesh)
    local_steps = 4

    param_shapes = jax.eval_shape(
        lambda k: transformer.init_model(cfg, k), jax.ShapeDtypeStruct((2,), jnp.uint32))
    stacked_shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n_clients,) + s.shape, s.dtype), param_shapes)
    stacked_sh = _named(
        jax.tree.map(lambda s: P(c_ax, *s), param_specs(param_shapes, rs),
                     is_leaf=lambda s: isinstance(s, P)), mesh)

    bspecs = model_zoo.batch_specs(cfg, shape)
    per_client_b = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            (local_steps, n_clients, s.shape[0] // n_clients) + s.shape[1:], s.dtype),
        bspecs)
    b_sh = jax.tree.map(
        lambda s: NamedSharding(mesh, P(None, c_ax, "data", *([None] * (len(s.shape) - 3)))),
        per_client_b)

    opt = sgd(1e-2)
    fl = fc.FedChainConfig(local_steps=local_steps)
    local = fc.make_local_steps_only(cfg, opt, fl, moe_groups=groups // n_clients or 1)
    sync = fc.make_sync_step(n_clients)

    # pod size for cross-pod bucketing: devices are pod-major in the mesh
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    pod_size = mesh.devices.size // sizes.get(c_ax, 1)

    results = {}
    with use_rules(rs):
        # repro: allow[R4] one-shot AOT lowering jit, never cached
        j_local = jax.jit(local, in_shardings=(stacked_sh, (), b_sh),
                          out_shardings=(stacked_sh, (), None), donate_argnums=(0,))
        lo = j_local.lower(stacked_shapes, (), per_client_b)
        co = lo.compile()
        results["local_phase"] = {
            "collectives": analysis.parse_collectives(co.as_text(), pod_size=pod_size),
            "cost": {k: v for k, v in analysis.cost_dict(co).items()
                     if isinstance(v, (int, float))},
            "memory": analysis.memory_summary(co.memory_analysis()),
        }

        j_sync = jax.jit(sync, in_shardings=(stacked_sh,), out_shardings=stacked_sh)
        co2 = j_sync.lower(stacked_shapes).compile()
        results["sync_step"] = {
            "collectives": analysis.parse_collectives(co2.as_text(), pod_size=pod_size),
            "memory": analysis.memory_summary(co2.memory_analysis()),
        }

        # global phase: plain synchronous step (the A_global baseline) — uses
        # the standard layout (batch over pod+data) since no client axis exists
        rs_global = RuleSet(mesh)
        step = model_zoo.make_train_step(cfg, opt, moe_groups=groups)
        p_sh = _named(param_specs(param_shapes, rs_global), mesh)
        b2 = _batch_shardings(cfg, shape, rs_global)
        with use_rules(rs_global):
            # repro: allow[R4] one-shot AOT lowering jit, never cached
            j_glob = jax.jit(step, in_shardings=(p_sh, (), b2),
                             out_shardings=(p_sh, (), None), donate_argnums=(0,))
            co3 = j_glob.lower(param_shapes, (), model_zoo.batch_specs(cfg, shape)).compile()
        results["global_step"] = {
            "collectives": analysis.parse_collectives(co3.as_text(), pod_size=pod_size),
            "cost": {k: v for k, v in analysis.cost_dict(co3).items()
                     if isinstance(v, (int, float))},
            "memory": analysis.memory_summary(co3.memory_analysis()),
        }

    return {"arch": arch, "mesh": mesh_name, "mode": "fedchain",
            "status": "ok", "phases": results,
            "local_steps_per_round": local_steps, "n_clients": n_clients}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--fedchain", action="store_true")
    ap.add_argument("--mla-absorb", action="store_true")
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--attn-fallback", action="store_true")
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--tag", default="", help="suffix for artifact filenames")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    out_dir = args.out or os.path.abspath(OUT_DIR)
    os.makedirs(out_dir, exist_ok=True)

    archs = list(registry.ARCH_IDS) if args.arch == "all" else args.arch.split(",")
    shapes = list(INPUT_SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod_16x16", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi_pod_2x16x16", make_production_mesh(multi_pod=True)))

    failures = 0
    for mesh_name, mesh in meshes:
        for arch in archs:
            if args.fedchain:
                tag = f"fedchain__{arch}__{mesh_name}"
                try:
                    rec = lower_fedchain(arch, mesh, mesh_name)
                    print(f"[ok] {tag}")
                except Exception as e:  # noqa: BLE001
                    rec = {"arch": arch, "mesh": mesh_name, "mode": "fedchain",
                           "status": "error", "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()}
                    failures += 1
                    print(f"[FAIL] {tag}: {e}")
                with open(os.path.join(out_dir, tag + ".json"), "w") as f:
                    json.dump(rec, f, indent=1)
                continue
            for shape_name in shapes:
                suffix = ""
                if args.mla_absorb:
                    suffix += "__absorb"
                if args.seq_shard:
                    suffix += "__seqshard"
                if args.attn_fallback:
                    suffix += "__attnfb"
                if args.fsdp:
                    suffix += "__fsdp"
                if args.tag:
                    suffix += f"__{args.tag}"
                tag = f"{arch}__{shape_name}__{mesh_name}{suffix}"
                path = os.path.join(out_dir, tag + ".json")
                try:
                    rec = lower_one(arch, shape_name, mesh, mesh_name,
                                    mla_absorb=args.mla_absorb,
                                    seq_shard=args.seq_shard,
                                    attn_fallback=args.attn_fallback,
                                    fsdp=args.fsdp,
                                    measure_depth=mesh_name.startswith("single"))
                    rec["variant"] = suffix.strip("_") or "baseline"
                    status = rec["status"]
                    extra = rec.get("reason", "")
                    if status == "ok":
                        r = rec["roofline"]
                        extra = (f"comp={r['compute_s']:.3e}s mem={r['memory_s']:.3e}s "
                                 f"coll={r['collective_s']:.3e}s dom={r['dominant']}")
                    print(f"[{status}] {tag} {extra}")
                except Exception as e:  # noqa: BLE001
                    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                           "status": "error", "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()}
                    failures += 1
                    print(f"[FAIL] {tag}: {e}")
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
    print(f"done; failures={failures}")
    return failures


if __name__ == "__main__":
    raise SystemExit(main())
