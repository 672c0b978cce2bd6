"""FedChain distributed-runtime semantics on CPU (single device where
possible; shard_map grouped collectives via subprocess for device isolation)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.launch import fedchain as fc
from repro.models import model_zoo, transformer
from repro.optim import sgd

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_broadcast_and_sync_roundtrip():
    cfg = registry.get_config("mamba2-1.3b", smoke=True)
    params = transformer.init_model(cfg, jax.random.PRNGKey(0))
    stacked = fc.broadcast_to_clients(params, 3)
    sync = fc.make_sync_step(3)
    merged = sync(stacked)
    for a, b in zip(jax.tree.leaves(stacked), jax.tree.leaves(merged)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=1e-5)


def test_local_round_clients_diverge_then_merge():
    """Different client data ⇒ replicas diverge during the round; the round
    boundary re-merges them to a common model (FedAvg semantics)."""
    import dataclasses

    from repro.configs import INPUT_SHAPES

    cfg = registry.get_config("qwen3-14b", smoke=True)
    shape = dataclasses.replace(INPUT_SHAPES["train_4k"], seq_len=32, global_batch=2)
    params = transformer.init_model(cfg, jax.random.PRNGKey(0))
    opt = sgd(0.2)
    c, steps = 2, 3
    fl = fc.FedChainConfig(local_steps=steps)
    local_only = fc.make_local_steps_only(cfg, opt, fl)
    client_p = fc.broadcast_to_clients(params, c)
    client_o = jax.vmap(opt.init)(client_p)
    batches = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1),
                                     (steps, c, 2, 32), 0, cfg.vocab_size)}
    new_p, _, losses = local_only(client_p, client_o, batches)
    # diverged: client 0 and 1 params differ somewhere
    diverged = any(
        float(jnp.max(jnp.abs(l[0].astype(jnp.float32) - l[1].astype(jnp.float32)))) > 1e-6
        for l in jax.tree.leaves(new_p))
    assert diverged
    merged = fc.make_sync_step(c)(new_p)
    for l in jax.tree.leaves(merged):
        np.testing.assert_allclose(np.asarray(l[0], np.float32),
                                   np.asarray(l[1], np.float32), rtol=1e-6)


def test_selection_step_picks_lower_loss():
    import dataclasses

    from repro.configs import INPUT_SHAPES

    cfg = registry.get_config("gemma3-4b", smoke=True)
    shape = dataclasses.replace(INPUT_SHAPES["train_4k"], seq_len=32, global_batch=2)
    params = transformer.init_model(cfg, jax.random.PRNGKey(0))
    # candidate B: slightly trained => lower loss
    batch = model_zoo.concrete_batch(cfg, shape, jax.random.PRNGKey(1))
    opt = sgd(0.3)
    step = jax.jit(model_zoo.make_train_step(cfg, opt))
    trained, s, _ = step(params, opt.init(params), batch)
    for _ in range(3):
        trained, s, _ = step(trained, s, batch)
    c = 2
    ca = fc.broadcast_to_clients(params, c)
    cb = fc.broadcast_to_clients(trained, c)
    probe = jax.tree.map(lambda t: jnp.stack([t, t]), batch)
    select = fc.make_selection_step(cfg)
    chosen, picked_a, (la, lb) = select(ca, cb, probe)
    assert float(lb) < float(la)
    assert not bool(picked_a)
    for l1, l2 in zip(jax.tree.leaves(chosen), jax.tree.leaves(cb)):
        np.testing.assert_allclose(np.asarray(l1, np.float32),
                                   np.asarray(l2, np.float32))


@pytest.mark.slow
def test_shardmap_grouped_fedavg_matches_reference():
    """Grouped-psum FedAvg round (shard_map + axis_index_groups) == the
    reference per-group computation, on 8 fake devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    code = textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.launch.mesh import make_mesh
        from repro.launch.fedchain_shardmap import run_grouped_fedavg_round, client_groups

        mesh = make_mesh((4, 2), ("data", "model"))
        # toy quadratic "model": params [d]; loss per batch row ||x - p||^2
        def loss_fn(p, batch):
            return jnp.mean(jnp.sum((batch - p[None, :]) ** 2, -1))

        d, steps, clients, lr = 8, 3, 2, 0.1
        params = jnp.zeros((d,))
        batches = jax.random.normal(jax.random.PRNGKey(0), (steps, 8, d))

        merged, loss = run_grouped_fedavg_round(
            loss_fn, params, batches, mesh=mesh, clients=clients, lr=lr, steps=steps)

        # reference: run each client group separately on its data half
        def client_run(p, bs):
            for t in range(steps):
                g = jax.grad(loss_fn)(p, bs[t])
                p = p - lr * g
            return p
        half = batches.shape[1] // clients
        ps = [client_run(params, batches[:, i*half:(i+1)*half]) for i in range(clients)]
        ref = sum(ps) / clients
        err = float(jnp.max(jnp.abs(merged - ref)))
        print(json.dumps({"err": err}))
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["err"] < 1e-5
