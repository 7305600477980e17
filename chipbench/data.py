"""The shared data split of the chip benchmark: per-node shards with
Dirichlet class skew.

A copy of the program's Dirichlet label split (``dirichlet_partition``:
per class, node shares from Dirichlet(alpha), cut at the rounded
cumulative shares, redrawn until every node holds at least two samples),
kept here so that the yardstick does not move when the program's copy
does.  A family's ``build`` (``chipbench/families/<family>.py``) makes the
samples and their labels and hands the labels to
:func:`dirichlet_partition`.

Every seed gets the same shard sizes in another order: the class-by-node
sample counts are one Dirichlet draw from ``SPLIT_SEED``, handed to the
nodes in a seed-drawn order.  Where the family's labels are a shuffle of
a fixed multiset, the largest shard, which sets the width of the
program's per-node index table and so the shapes of its compiled
superstep, is then the same for every seed, and every run after the
first finds its programs in the compilation cache.
"""
from __future__ import annotations

import numpy as np

SPLIT_SEED = 0            # the one draw of the class-by-node counts


def dirichlet_counts(class_sizes, n_nodes: int, alpha: float,
                     rng: np.random.Generator,
                     min_per_node: int = 2) -> np.ndarray:
    """``[classes, n_nodes]`` sample counts with Dirichlet(alpha) class
    skew: per class, node shares from Dirichlet(alpha) cut at the rounded
    cumulative shares (count-conserving), the whole draw repeated (up to
    100 times) until every node holds ``min_per_node`` samples."""
    for _ in range(100):
        counts = []
        for size in class_sizes:
            props = rng.dirichlet(np.full(n_nodes, alpha))
            cuts = np.round(np.cumsum(props)[:-1] * size).astype(int)
            counts.append(np.diff(np.concatenate([[0], cuts, [size]])))
        counts = np.stack(counts)
        if counts.sum(axis=0).min() >= min_per_node:
            return counts
    raise RuntimeError(
        f"no Dirichlet({alpha}) split of {sum(class_sizes)} samples gives "
        f"each of {n_nodes} nodes {min_per_node} samples in 100 draws")


def dirichlet_partition(labels: np.ndarray, n_nodes: int, alpha: float,
                        rng: np.random.Generator) -> list:
    """Per-node sorted sample indices: the class-by-node counts of
    :func:`dirichlet_counts` (drawn from ``SPLIT_SEED``), their node
    columns handed out in an order drawn from ``rng``, each class's
    samples shuffled by ``rng`` before they are cut."""
    labels = np.asarray(labels)
    classes, sizes = np.unique(labels, return_counts=True)
    counts = dirichlet_counts(sizes, n_nodes, alpha,
                              np.random.default_rng(SPLIT_SEED))
    owner = rng.permutation(n_nodes)
    node = np.empty(len(labels), np.int64)
    for c, cls in enumerate(classes):
        idx = np.flatnonzero(labels == cls)
        rng.shuffle(idx)
        node[idx] = np.repeat(owner, counts[c])
    order = np.argsort(node, kind="stable")
    bounds = np.cumsum(np.bincount(node, minlength=n_nodes))[:-1]
    return np.split(order, bounds)
