"""The benchmark's own population has the layout of the program's
generator (data.synthetic_vision + data.partition.pathological_shards):
the same shapes, two whole label shards per client, every shard dealt once.
It is the benchmark's data and need not match the program's bitwise.

It is found by name (``populations/mnist-shards.py``), and the same seed
gives bitwise the arrays it gave when it was ``chipbench/population.py``."""
from __future__ import annotations

import hashlib

import jax
import numpy as np

from chipbench import harness
from conftest import small_cell

MNIST_SHARDS = harness.load_module("populations", "mnist-shards")
make_population = MNIST_SHARDS.make_population

# sha256 over (dtype, shape, bytes) of features then labels, on the CPU,
# from chipbench/population.py as it stood before the move
SMALL_DIGEST = ("32ee982d54da68e2472a8ece6ec94259"
                "f42edcc63a87571761e0e06e6afa156c")
CELL_DIGEST = ("bb6e5ed67d31b6b6e3ffce3adf4b60cb"
               "24cc0af7f995bb66f6889e5cdefeb1a0")


def _digest(*arrays):
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _layout(labels, shard):
    """Per client: its labels come as whole shards of one class each."""
    out = []
    for row in labels:
        blocks = row.reshape(-1, shard)
        assert (blocks == blocks[:, :1]).all()
        out.append(tuple(blocks[:, 0]))
    return out


def test_layout_matches_the_program_generator():
    from repro.data import partition, synthetic_vision

    n, per, side, classes = 20, 60, 8, 10
    feats, labels = make_population(
        jax.random.PRNGKey(3), num_clients=n, per_client=per, side=side,
        classes=classes, shards_per_client=2, noise=0.35)
    feats, labels = np.asarray(feats), np.asarray(labels)
    images = synthetic_vision.make_prototype_images(
        num_classes=classes, per_class=n * per // classes, side=side, seed=3)
    px = images.reshape(n * per, -1)
    py = np.repeat(np.arange(classes), n * per // classes)
    want_x, want_y = partition.pathological_shards(
        px - px.mean(0), py, num_clients=n, shards_per_client=2, seed=3)
    assert feats.shape == want_x.shape == (n, per, side * side)
    assert labels.shape == want_y.shape == (n, per)
    shard = per // 2
    ours, theirs = _layout(labels, shard), _layout(want_y, shard)
    assert all(len(s) == 2 for s in ours + theirs)
    # every class is dealt as often as in the program's split
    assert (np.bincount(labels.ravel(), minlength=classes)
            == np.bincount(want_y.ravel(), minlength=classes)).all()
    # pixels centred on the population mean, in the range a [0, 1] image
    # minus its mean can take
    assert abs(float(feats.mean())) < 1e-5
    assert feats.min() >= -1.0 and feats.max() <= 1.0


def test_full_size_is_the_mnist_split():
    feats, labels = jax.eval_shape(
        lambda k: make_population(k, num_clients=100, per_client=600,
                                  side=28, classes=10, shards_per_client=2,
                                  noise=0.35), jax.random.PRNGKey(0))
    assert feats.shape == (100, 600, 784) and labels.shape == (100, 600)


def test_same_seed_same_population():
    a = make_population(jax.random.PRNGKey(9), num_clients=10, per_client=20,
                        side=4, classes=10, shards_per_client=2, noise=0.35)
    b = make_population(jax.random.PRNGKey(9), num_clients=10, per_client=20,
                        side=4, classes=10, shards_per_client=2, noise=0.35)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))


def test_bitwise_the_population_before_the_move():
    small = make_population(
        jax.random.PRNGKey(3), num_clients=20, per_client=60, side=8,
        classes=10, shards_per_client=2, noise=0.35)
    assert _digest(*small) == SMALL_DIGEST
    # through the harness: a configuration that names no population
    cell = small_cell("logreg.c01-qsgd4ef")
    assert "population" not in cell.config
    cell.setup(20261019)
    assert set(cell.data) == {"features", "classes"}
    assert _digest(cell.data["features"], cell.data["classes"]) == CELL_DIGEST
