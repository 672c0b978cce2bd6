"""End-to-end training driver.

Modes:
  * plain synchronous training (``--fl-mode none``) — the A_global baseline;
  * FedChain (``--fl-mode fedchain``) — local-update phase with per-client
    replicas and zero cross-client collectives, Lemma H.2 selection, then the
    synchronous global phase (the paper's Algo 1 as a systems feature).

CPU-runnable end-to-end with ``--smoke`` (reduced configs, synthetic token
stream); the same code path drives the production meshes on TPU.

Example:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-14b --smoke \
      --steps 60 --fl-mode fedchain --clients 4 --local-steps 4 --local-rounds 4
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import save_checkpoint
from repro.configs import registry
from repro.data.tokens import SyntheticTokenStream, TokenStreamConfig
from repro.launch import fedchain as fc
from repro.models import model_zoo, transformer
from repro.optim import get_optimizer


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--optimizer", default="sgd", choices=["sgd", "momentum", "adamw"])
    ap.add_argument("--fl-mode", default="none", choices=["none", "fedchain"])
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=4, help="K between syncs")
    ap.add_argument("--local-rounds", type=int, default=4)
    ap.add_argument("--heterogeneity", type=float, default=1.0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-path", default=None, help="JSONL metrics file")
    ap.add_argument("--microbatches", type=int, default=1,
                    help=">1 enables gradient accumulation (memory lever)")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def run_plain(cfg, args):
    from repro.launch.metrics import MetricsLogger

    key = jax.random.PRNGKey(args.seed)
    params = transformer.init_model(cfg, key)
    opt = get_optimizer(args.optimizer, args.lr)
    opt_state = opt.init(params)
    if args.microbatches > 1:
        from repro.optim.accumulate import make_accumulating_train_step

        def loss_fn(p, b):
            return transformer.lm_loss(p, cfg, b)

        step_fn = jax.jit(make_accumulating_train_step(
            loss_fn, opt, microbatches=args.microbatches))
    else:
        step_fn = jax.jit(model_zoo.make_train_step(cfg, opt))

    stream = SyntheticTokenStream(TokenStreamConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq, batch_size=args.batch,
        num_clients=1, seed=args.seed))

    losses = []
    t0 = time.time()
    with MetricsLogger(args.metrics_path) as logger:
        for step in range(args.steps):
            batch = _full_batch(cfg, stream.batch(0, step), args)
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            losses.append(float(metrics["loss"]))
            logger.log(step, loss=losses[-1])
            if step % args.log_every == 0:
                print(f"step {step:5d} loss {losses[-1]:.4f} "
                      f"({(time.time()-t0)/(step+1):.2f}s/step)")
            if (args.ckpt_every and args.ckpt_dir
                    and (step + 1) % args.ckpt_every == 0):
                save_checkpoint(args.ckpt_dir, step + 1, params)
    return params, losses


def _full_batch(cfg, batch, args):
    """Attach stub frontend inputs for VLM/audio archs."""
    out = dict(batch)
    b = batch["tokens"].shape[0]
    if cfg.frontend is not None and cfg.frontend.kind == "vision":
        out["image_embeds"] = jnp.zeros((b, cfg.frontend.seq, cfg.frontend.dim),
                                        cfg.param_dtype())
    if cfg.encoder is not None:
        out["frames"] = jnp.zeros((b, cfg.frontend.seq, cfg.frontend.dim),
                                  cfg.param_dtype())
    return out


def run_fedchain(cfg, args):
    """FedChain (Algo 1) over simulated client groups:
    local rounds (K steps each, per-client replicas) → selection → global."""
    from repro.launch.metrics import MetricsLogger

    key = jax.random.PRNGKey(args.seed)
    c = args.clients
    params0 = transformer.init_model(cfg, key)
    opt = get_optimizer(args.optimizer, args.lr)
    fl = fc.FedChainConfig(local_rounds=args.local_rounds,
                           local_steps=args.local_steps)

    stream = SyntheticTokenStream(TokenStreamConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq, batch_size=args.batch,
        num_clients=c, heterogeneity=args.heterogeneity, seed=args.seed))

    local_round = jax.jit(fc.make_local_round(cfg, opt, fl, n_clients=c))
    select = jax.jit(fc.make_selection_step(cfg))
    global_step = jax.jit(fc.make_global_step(cfg, opt))

    def client_batches(step0, steps):
        def stack(fn):
            return jnp.stack([jnp.stack([fn(ci, step0 + s) for ci in range(c)])
                              for s in range(steps)])

        toks = stack(lambda ci, s: _full_batch(cfg, stream.batch(ci, s), args)["tokens"])
        out = {"tokens": toks}
        b = toks.shape[-2]
        if cfg.frontend is not None and cfg.frontend.kind == "vision":
            out["image_embeds"] = jnp.zeros(
                (steps, c, b, cfg.frontend.seq, cfg.frontend.dim), cfg.param_dtype())
        if cfg.encoder is not None:
            out["frames"] = jnp.zeros(
                (steps, c, b, cfg.frontend.seq, cfg.frontend.dim), cfg.param_dtype())
        return out

    # ---- phase 1: A_local (FedAvg) ----------------------------------------
    client_p = fc.broadcast_to_clients(params0, c)
    client_o = jax.vmap(opt.init)(client_p)
    losses = []
    step0 = 0
    with MetricsLogger(args.metrics_path) as logger:
        for r in range(fl.local_rounds):
            batches = client_batches(step0, fl.local_steps)
            client_p, client_o, loss = local_round(client_p, client_o,
                                                   batches)
            step0 += fl.local_steps
            losses.append(float(loss))
            logger.log(step0, loss=losses[-1], phase=0.0, local_round=r)
            print(f"[local round {r}] loss {loss:.4f}")

        # ---- selection (Lemma H.2) ----------------------------------------
        probe = client_batches(step0, 1)
        probe = jax.tree.map(lambda t: t[0], probe)  # [C, b, ...]
        cand_a = fc.broadcast_to_clients(params0, c)
        chosen, picked_init, (la, lb) = select(cand_a, client_p, probe)
        print(f"[selection] F(x0)={float(la):.4f} F(x_half)={float(lb):.4f} "
              f"kept {'x0' if bool(picked_init) else 'x_half'}")

        # ---- phase 2: A_global (synchronous SGD) --------------------------
        params = jax.tree.map(lambda t: t[0], chosen)
        opt_state = opt.init(params)
        remaining = max(0, args.steps - fl.local_rounds * fl.local_steps)
        for step in range(remaining):
            batch = _full_batch(cfg, stream.batch(step % c, step0 + step),
                                args)
            params, opt_state, metrics = global_step(params, opt_state,
                                                     batch)
            losses.append(float(metrics["loss"]))
            logger.log(step0 + step, loss=losses[-1], phase=1.0)
            if step % args.log_every == 0:
                print(f"[global step {step}] loss {losses[-1]:.4f}")
    return params, losses


def main(argv=None):
    args = parse_args(argv)
    from repro import compile_cache

    compile_cache.enable()
    cfg = registry.get_config(args.arch, smoke=args.smoke)
    if args.smoke:
        cfg = dataclasses.replace(cfg, max_seq_len=max(args.seq * 2, 256))
    print(f"arch={cfg.name} params≈{model_zoo.param_count(cfg):,} "
          f"fl_mode={args.fl_mode}")
    if args.fl_mode == "fedchain":
        params, losses = run_fedchain(cfg, args)
    else:
        params, losses = run_plain(cfg, args)
    result = {"arch": cfg.name, "fl_mode": args.fl_mode,
              "first_loss": losses[0], "final_loss": losses[-1]}
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
