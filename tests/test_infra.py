"""Infrastructure tests: sharding rules, checkpointing, optimizers, data."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import adamw, momentum, sgd, Schedule
from repro.sharding.rules import param_logical_axes, cache_logical_axes


# --------------------------- sharding rules ---------------------------------

def test_param_rules_match_names():
    assert param_logical_axes("seg0/attn/wq", 3) == ("embed", "heads", "head_dim")
    assert param_logical_axes("seg0/attn/wq", 4) == ("layers", "embed", "heads", "head_dim")
    assert param_logical_axes("embed/embedding", 2) == ("vocab", "embed")
    assert param_logical_axes("seg1/moe/we_gate", 4) == ("layers", "experts", "embed", "ff")
    assert param_logical_axes("unknown/leaf", 2) == (None, None)


def test_cache_rules():
    assert cache_logical_axes("/seg0/k", 5) == (
        "layers", "batch", "cache_seq", "kv_heads", "head_dim")
    assert cache_logical_axes("/seg0/c_kv", 4) == (
        "layers", "batch", "cache_seq", "kv_lora")
    assert cache_logical_axes("/mamba/ssm", 5) == (
        "layers", "batch", "heads", "head_dim", "ssm_state")


def test_ruleset_divisibility_and_dedup():
    from repro.launch.mesh import make_mesh
    from repro.sharding import RuleSet

    mesh = make_mesh((1,), ("model",))
    rs = RuleSet(mesh)
    # axis size 1 always divides
    spec = rs.spec_for(("experts", "embed", "ff"), (4, 8, 16))
    # 'model' must appear at most once
    used = [s for s in spec if s is not None]
    assert len(used) <= 1


# --------------------------- checkpoint --------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import latest_step, restore, save_checkpoint

    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
    d = str(tmp_path / "ckpt")
    save_checkpoint(d, 10, tree)
    save_checkpoint(d, 20, jax.tree.map(lambda t: t + 1, tree))
    assert latest_step(d) == 20
    back = restore(d, 20, tree)
    np.testing.assert_allclose(np.asarray(back["a"]), np.asarray(tree["a"]) + 1)
    assert back["b"]["c"].dtype == jnp.bfloat16


def test_checkpoint_rotation(tmp_path):
    from repro.checkpoint import latest_steps, save_checkpoint

    tree = {"x": jnp.zeros((2,))}
    d = str(tmp_path / "ck")
    for s in range(6):
        save_checkpoint(d, s, tree, keep=3)
    assert latest_steps(d) == [3, 4, 5]


def test_checkpoint_shape_mismatch_raises(tmp_path):
    from repro.checkpoint import restore, save_checkpoint

    d = str(tmp_path / "ck2")
    save_checkpoint(d, 1, {"x": jnp.zeros((2,))})
    with pytest.raises(ValueError):
        restore(d, 1, {"x": jnp.zeros((3,))})


# --------------------------- optimizers --------------------------------------

def _quad_grad(p):
    return jax.tree.map(lambda t: 2 * t, p)


@pytest.mark.parametrize("opt", [sgd(0.1), momentum(0.1), adamw(0.1)],
                         ids=["sgd", "momentum", "adamw"])
def test_optimizers_descend(opt):
    params = {"w": jnp.asarray([4.0, -2.0])}
    state = opt.init(params)
    for _ in range(50):
        params, state = opt.update(_quad_grad(params), state, params)
    assert float(jnp.abs(params["w"]).max()) < 0.3


def test_momentum_dtype_preserved():
    opt = momentum(0.1)
    params = {"w": jnp.ones((3,), jnp.bfloat16)}
    state = opt.init(params)
    p2, _ = opt.update({"w": jnp.ones((3,), jnp.bfloat16)}, state, params)
    assert p2["w"].dtype == jnp.bfloat16


def test_schedule():
    s = Schedule(base_lr=1.0, warmup_steps=10, decay_every=100, decay_factor=0.5)
    assert float(s(0)) == pytest.approx(0.1)
    assert float(s(9)) == pytest.approx(1.0)
    assert float(s(150)) == pytest.approx(0.5)


# --------------------------- data --------------------------------------------

def test_shuffled_heterogeneity_partition():
    from repro.data.partition import shuffled_heterogeneity

    feats = np.random.default_rng(0).normal(size=(10, 40, 7)).astype(np.float32)
    for frac in (0.0, 0.5, 1.0):
        cx, cy = shuffled_heterogeneity(
            feats, homogeneous_frac=frac, num_clients=5, seed=1)
        assert cx.shape[0] == 5 and cx.shape[2] == 7
        assert cy.shape[:2] == cx.shape[:2]
    # 0% homogeneous: client i holds only classes 2i, 2i+1
    cx, cy = shuffled_heterogeneity(feats, homogeneous_frac=0.0, num_clients=5)
    assert set(np.unique(cy[0])) == {0, 1}
    assert set(np.unique(cy[4])) == {8, 9}


def test_pathological_shards_partition():
    from repro.data.partition import pathological_shards

    n = 1200  # 10 classes × 120 → 20 shards of 60, two per client
    labels = np.repeat(np.arange(10), 120)
    feats = np.arange(n, dtype=np.float32)[:, None] * np.ones((1, 3))
    cx, cy = pathological_shards(feats, labels, num_clients=10, seed=3)
    assert cx.shape == (10, 120, 3) and cy.shape == (10, 120)
    # every sample dealt exactly once, features travel with their labels
    np.testing.assert_array_equal(np.sort(cx[..., 0].ravel()), np.arange(n))
    np.testing.assert_array_equal(labels[cx[..., 0].astype(int)], cy)
    # each client holds two label-sorted shards: at most two classes
    assert max(len(np.unique(c)) for c in cy) <= 2


def test_compile_cache_dir(monkeypatch):
    from repro import compile_cache

    seen = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: seen.append((name, value)))
    monkeypatch.setenv(compile_cache.ENV, "/somewhere/else")
    assert compile_cache.enable() == "/somewhere/else"
    assert seen == []  # JAX reads the variable itself
    monkeypatch.delenv(compile_cache.ENV)
    assert compile_cache.enable() == compile_cache.DEFAULT_DIR
    assert seen == [("jax_compilation_cache_dir", compile_cache.DEFAULT_DIR)]
    assert compile_cache.DEFAULT_DIR.endswith(os.sep + ".jax_cache")


def test_hardware_constants_keyed_by_device_kind():
    from repro.launch.mesh import hardware_constants

    assert hardware_constants("TPU v5 lite")["hbm_bw"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        hardware_constants("cpu")


def test_token_stream_deterministic():
    from repro.data.tokens import SyntheticTokenStream, TokenStreamConfig

    cfg = TokenStreamConfig(vocab_size=64, seq_len=16, batch_size=2,
                            num_clients=3, heterogeneity=0.5)
    s1 = SyntheticTokenStream(cfg)
    s2 = SyntheticTokenStream(cfg)
    b1 = s1.batch(1, 7)["tokens"]
    b2 = s2.batch(1, 7)["tokens"]
    np.testing.assert_array_equal(np.asarray(b1), np.asarray(b2))
    b3 = s1.batch(2, 7)["tokens"]
    assert not np.array_equal(np.asarray(b1), np.asarray(b3))


def test_synthetic_vision_shapes():
    from repro.data.synthetic_vision import binary_labels_even_odd, make_prototype_images

    data = make_prototype_images(num_classes=4, per_class=10, side=8)
    assert data.shape == (4, 10, 64)
    assert data.min() >= 0 and data.max() <= 1
    labels = binary_labels_even_odd(np.asarray([0, 1, 2, 3]))
    np.testing.assert_array_equal(labels, [0, 1, 0, 1])
