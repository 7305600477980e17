"""Synthetic image data for the chip benchmark, made on the device from a seed.

A copy of the program's generator (``make_image_classification``: class
prototypes of smooth noise, per-sample smooth noise, optional per-writer
style shifts, clipped to [-2, 2]) and of its Dirichlet label split
(``dirichlet_partition``: per class, node shares from Dirichlet(alpha),
cut at the rounded cumulative shares, redrawn until every node holds at
least two samples).  They are kept here so that the yardstick does not move
when the program's copies do.

Every seed gets the same sizes in another order: the labels are a shuffle
of a fixed multiset (each class ``N / classes`` times), and the
class-by-node sample counts are one Dirichlet draw from ``SPLIT_SEED``,
handed to the nodes in a seed-drawn order.  The largest shard, which sets
the width of the program's per-node index table and so the shapes of its
compiled superstep, is then the same for every seed, and every run after
the first finds its programs in the compilation cache.

The images are drawn with ``jax.random`` in blocks under ``lax.map``, in
one jitted call: nothing image-sized is made on the host, and the
temporaries of one block, not of the whole set, set the generator's peak
memory.  Only the labels come to the host, for the split.
"""
from __future__ import annotations

import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

# Per-sample noise scale of the synthetic images: the hard setting the
# chip runs of the program used (class prototypes buried in noise), so
# that 25 rounds of training leave accuracy far from 1.
NOISE = 3.0
BLOCK_MAX = 2048          # images made per lax.map step
SPLIT_SEED = 0            # the one draw of the class-by-node counts


def _block(n: int) -> int:
    """Largest divisor of ``n`` that is at most ``BLOCK_MAX``."""
    return max(d for d in range(1, min(n, BLOCK_MAX) + 1) if n % d == 0)


def _smooth(key, shape, passes):
    """Spatially smooth noise: the mean of each pixel and its four
    neighbours (wrapping), ``passes`` times, over the H and W axes."""
    x = jax.random.normal(key, shape, jnp.float32)
    for _ in range(passes):
        x = (x + jnp.roll(x, 1, axis=-3) + jnp.roll(x, 1, axis=-2)
             + jnp.roll(x, -1, axis=-3) + jnp.roll(x, -1, axis=-2)) / 5.0
    return x


@functools.partial(jax.jit, static_argnames=(
    "n_train", "n_test", "num_classes", "image_size", "channels",
    "writers"))
def _make(key, *, n_train, n_test, num_classes, image_size, channels,
          writers):
    shape = (image_size, image_size, channels)
    kp, ks, ktrain, ktest = jax.random.split(key, 4)
    protos = _smooth(kp, (num_classes,) + shape, 2)
    protos = protos / jnp.abs(protos).max(axis=(1, 2, 3), keepdims=True)
    styles = _smooth(ks, (writers,) + shape, 2) * 0.4 \
        if writers > 1 else None

    def split(k, n):
        kl, kw, kn = jax.random.split(k, 3)
        labels = jax.random.permutation(
            kl, jnp.arange(n, dtype=jnp.int32) % num_classes)
        wid = jax.random.randint(kw, (n,), 0, writers, jnp.int32)
        b = _block(n)

        def one(args):
            i, lab, w = args
            x = protos[lab] + NOISE * _smooth(jax.random.fold_in(kn, i),
                                              (b,) + shape, 1)
            if styles is not None:
                x = x + styles[w]
            return jnp.clip(x, -2.0, 2.0)

        images = jax.lax.map(one, (jnp.arange(n // b),
                                   labels.reshape(n // b, b),
                                   wid.reshape(n // b, b)))
        return images.reshape((n,) + shape), labels

    return split(ktrain, n_train), split(ktest, n_test)


def make_images(key, *, n_train: int, n_test: int, num_classes: int,
                image_size: int, channels: int, writers: int = 1):
    """``(train_images, train_labels), (test_images, test_labels)`` on the
    device: ``[N, H, W, C]`` float32 and ``[N]`` int32, train and test
    drawn alike from the same prototypes and writer styles."""
    return _make(key, n_train=n_train, n_test=n_test,
                 num_classes=num_classes, image_size=image_size,
                 channels=channels, writers=writers)


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


def build(seed: int, model: dict, traffic: dict):
    """The cell's data from ``seed``: the device-resident train set (an
    object with ``images`` and ``labels``, the layout the program's
    device data stream takes), the per-node index lists of the split,
    and the test batch."""
    (xtr, ytr), (xte, yte) = make_images(
        jax.random.PRNGKey(seed), n_train=traffic["train"],
        n_test=traffic["test"], num_classes=model["num_classes"],
        image_size=model["image_size"], channels=model["in_channels"],
        writers=traffic["nodes"] if model.get("writer_styles") else 1)
    parts = dirichlet_partition(np.asarray(ytr), traffic["nodes"],
                                traffic["alpha"],
                                np.random.default_rng(seed))
    return (SimpleNamespace(images=xtr, labels=ytr), parts,
            {"images": xte, "labels": yte})
