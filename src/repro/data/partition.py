"""Client partitioners, including the paper's "X% homogeneous" shuffling
scheme (§6 / App. I.1).

The paper controls heterogeneity by shuffling the first X% of each class's
samples uniformly across clients, and assigning the remaining (100−X)% of
classes 2i−2 and 2i−1 to client i. 100% homogeneous is *not* ζ = 0 (sampling
randomness remains) — exactly as the paper notes.
"""
from __future__ import annotations

import numpy as np


def shuffled_heterogeneity(
    features: np.ndarray,  # [num_classes, per_class, ...]
    *,
    homogeneous_frac: float,
    num_clients: int,
    seed: int = 0,
):
    """Returns (client_features [N, n_i, ...], client_labels [N, n_i]).

    Requires num_classes == 2 * num_clients (paper: 10 digits, 5 clients).
    """
    rng = np.random.default_rng(seed)
    num_classes, per_class = features.shape[:2]
    assert num_classes == 2 * num_clients, "paper scheme: 2 classes per client"
    n_hom = int(round(homogeneous_frac * per_class))

    # homogeneous pool: first n_hom of every class, shuffled, split evenly
    pool_x = features[:, :n_hom].reshape((-1,) + features.shape[2:])
    pool_y = np.repeat(np.arange(num_classes), n_hom)
    perm = rng.permutation(pool_x.shape[0])
    pool_x, pool_y = pool_x[perm], pool_y[perm]
    # make divisible
    per_client_pool = pool_x.shape[0] // num_clients
    pool_x = pool_x[: per_client_pool * num_clients]
    pool_y = pool_y[: per_client_pool * num_clients]
    pool_x = pool_x.reshape((num_clients, per_client_pool) + features.shape[2:])
    pool_y = pool_y.reshape(num_clients, per_client_pool)

    # heterogeneous remainder: client i gets classes 2i, 2i+1 (0-based)
    client_x, client_y = [], []
    for i in range(num_clients):
        xs = [pool_x[i]]
        ys = [pool_y[i]]
        for c in (2 * i, 2 * i + 1):
            xs.append(features[c, n_hom:])
            ys.append(np.full(per_class - n_hom, c))
        client_x.append(np.concatenate(xs, axis=0))
        client_y.append(np.concatenate(ys, axis=0))

    n_min = min(x.shape[0] for x in client_x)
    client_x = np.stack([x[:n_min] for x in client_x])
    client_y = np.stack([y[:n_min] for y in client_y])
    return client_x, client_y


def pathological_shards(
    features: np.ndarray,  # [n, ...]
    labels: np.ndarray,  # [n]
    *,
    num_clients: int,
    shards_per_client: int = 2,
    seed: int = 0,
):
    """The pathological non-IID split of McMahan et al. 2017
    (arXiv:1602.05629, §3): sort the samples by label, cut them into
    ``num_clients · shards_per_client`` equal shards and deal each client
    ``shards_per_client`` shards at random (MNIST: 200 shards of 300, two
    per client, 100 clients × 600). Returns (client_features [N, n_i, ...],
    client_labels [N, n_i]); a remainder of fewer than one shard is dropped.
    """
    rng = np.random.default_rng(seed)
    n_shards = num_clients * shards_per_client
    size = len(labels) // n_shards
    order = np.argsort(labels, kind="stable")[: n_shards * size]
    deal = rng.permutation(n_shards).reshape(num_clients, shards_per_client)
    idx = order.reshape(n_shards, size)[deal].reshape(num_clients, -1)
    return features[idx], labels[idx]


def dirichlet_partition(labels: np.ndarray, *, num_clients: int, alpha: float, seed: int = 0):
    """Standard Dirichlet(α) label-skew partition; returns index lists."""
    rng = np.random.default_rng(seed)
    classes = np.unique(labels)
    client_idx = [[] for _ in range(num_clients)]
    for c in classes:
        idx = np.where(labels == c)[0]
        rng.shuffle(idx)
        props = rng.dirichlet(np.full(num_clients, alpha))
        cuts = (np.cumsum(props) * len(idx)).astype(int)[:-1]
        for i, part in enumerate(np.split(idx, cuts)):
            client_idx[i].extend(part.tolist())
    return [np.asarray(ix) for ix in client_idx]


def by_class_partition(labels: np.ndarray, *, num_clients: int):
    """Maximally heterogeneous: contiguous class blocks per client."""
    classes = np.unique(labels)
    per = max(1, len(classes) // num_clients)
    client_idx = []
    for i in range(num_clients):
        cs = classes[i * per: (i + 1) * per]
        client_idx.append(np.where(np.isin(labels, cs))[0])
    return client_idx
