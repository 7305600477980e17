"""The CNN family: image classifiers stated as a layer list.

A configuration of this family (``"family": "cnn"``) states its
published architecture as ``layers``, in order, over ``image_size`` x
``image_size`` x ``in_channels`` images of ``num_classes`` classes.

* Data: synthetic images made on the device from the seed (:func:`build`).
* Program node: the program's model module (``repro.models.cnn``) builds
  only its own two-stage CNN, so the node is assembled from that module's
  layer functions: its SAME convolution, its GroupNorm and its 2x2
  max-pool; a linear layer is ``x @ w + b`` as the module writes its head.
  The parameter layout (``conv<i>``, ``gn<i>``, ``fc<i>``) and the seeded
  start are the reference half's (``cnn_reference.py``).  The batches come
  from the program's ``DeviceDataStream`` over the device-made train set.
* Counts, as multiply-adds times two, from shapes alone: the forward per
  sample is each convolution (``k x k``, SAME) over its input's H x W and
  each linear layer's ``in x out``; GroupNorm, ReLU and pooling are not
  counted.  Training per sample is the forward, the weight gradients (as
  many MACs again), and the input gradients of every layer but the first
  (nothing needs the gradient of the images).

The data is a copy of the program's generator (``make_image_classification``:
class prototypes of smooth noise, per-sample smooth noise, optional
per-writer style shifts, clipped to [-2, 2]), kept here so that the
yardstick does not move when the program's copy does.  The labels are a
shuffle of a fixed multiset (each class ``N / classes`` times), so with
the split of ``chipbench/data.py`` every seed gets the same shard sizes in
another order.  The images are drawn with ``jax.random`` in blocks under
``lax.map``, in one jitted call: nothing image-sized is made on the host,
and the temporaries of one block, not of the whole set, set the
generator's peak memory.  Only the labels come to the host, for the split.
"""
from __future__ import annotations

import functools
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import data, harness

reference = harness.load_module(Path(__file__).with_name("cnn_reference.py"))

# Per-sample noise scale of the synthetic images: the hard setting the
# chip runs of the program used (class prototypes buried in noise), so
# that 25 rounds of training leave accuracy far from 1.
NOISE = 3.0
BLOCK_MAX = 2048          # images made per lax.map step


# ---------------------------------------------------------------------------
# Data
# ---------------------------------------------------------------------------

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


def build(seed: int, model: dict, traffic: dict):
    """The cell's data from ``seed``: the device-resident train set
    (``images``, ``labels``), the per-node index lists of the split, and
    the test batch."""
    (xtr, ytr), (xte, yte) = make_images(
        jax.random.PRNGKey(seed), n_train=traffic["train"],
        n_test=traffic["test"], num_classes=model["num_classes"],
        image_size=model["image_size"], channels=model["in_channels"],
        writers=traffic["nodes"] if model.get("writer_styles") else 1)
    parts = data.dirichlet_partition(np.asarray(ytr), traffic["nodes"],
                                     traffic["alpha"],
                                     np.random.default_rng(seed))
    return ({"images": xtr, "labels": ytr}, parts,
            {"images": xte, "labels": yte})


# ---------------------------------------------------------------------------
# Counts
# ---------------------------------------------------------------------------

def _walk(model: dict):
    """``(layer, input H, input channels or features)`` for each layer."""
    h, c = model["image_size"], model["in_channels"]
    for layer in model["layers"]:
        yield layer, h, c
        kind = layer[0]
        if kind == "conv":
            c = layer[1]
        elif kind == "pool":
            h //= 2
        elif kind == "flatten":
            c, h = h * h * c, None
        elif kind == "dense":
            c = layer[1]


def forward_macs(model: dict) -> dict:
    """MACs per sample of each weighted layer's forward pass, in order,
    keyed ``conv<i>`` or ``fc<i>`` by its place among them."""
    out = {}
    for layer, h, c in _walk(model):
        if layer[0] == "conv":
            out[f"conv{len(out) + 1}"] = h * h * layer[1] * layer[2] ** 2 * c
        elif layer[0] == "dense":
            out[f"fc{len(out) + 1}"] = c * layer[1]
    return out


def param_count(model: dict) -> int:
    """D: parameters of one node model."""
    total = 0
    for layer, h, c in _walk(model):
        if layer[0] == "conv":
            total += layer[2] ** 2 * c * layer[1] + layer[1]
        elif layer[0] == "group_norm":
            total += 2 * c
        elif layer[0] == "dense":
            total += c * layer[1] + layer[1]
    return total


def train_flops_per_sample(model: dict) -> int:
    macs = list(forward_macs(model).values())
    fwd = sum(macs)
    return 2 * (fwd + fwd + (fwd - macs[0]))


def eval_flops(model: dict, traffic: dict) -> int:
    """Every node's forward over the test set."""
    return (2 * sum(forward_macs(model).values()) * traffic["nodes"]
            * traffic["test"])


def round_flops(model: dict, traffic: dict, edges: int,
                negotiates: bool) -> int:
    """One round: every node's local step, the mixing over ``edges``
    in-edges, and the Eq.-3 Gram when the round negotiates."""
    n, d = traffic["nodes"], param_count(model)
    gram = 2 * n * n * d if negotiates else 0
    return (n * traffic["batch"] * train_flops_per_sample(model)
            + 2 * (edges + n) * d + gram)


# ---------------------------------------------------------------------------
# Program node
# ---------------------------------------------------------------------------

def forward(p, images, arch: tuple):
    """Logits ``[b, classes]`` of ``images [b, H, W, C]``."""
    from repro.models import cnn

    names = iter(name for name, *_ in reference.param_layers(arch))
    x = images
    for layer in arch[2]:
        kind = layer[0]
        if kind == "conv":
            x = cnn._conv(p[next(names)], x)
        elif kind == "group_norm":
            x = cnn._group_norm(p[next(names)], x, groups=layer[1])
        elif kind == "relu":
            x = jax.nn.relu(x)
        elif kind == "pool":
            x = cnn._pool(x)
        elif kind == "flatten":
            x = x.reshape(x.shape[0], -1)
        else:
            q = p[next(names)]
            x = x @ q["w"] + q["b"]
    return x


def loss_fn(arch: tuple):
    """``loss(p, batch) -> (mean cross-entropy, {"loss", "accuracy"})``,
    the signature of the program's ``cnn_loss``, for ``arch``."""
    def loss(p, batch):
        logits = forward(p, batch["images"], arch)
        labels = batch["labels"]
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
        mean = nll.mean()
        acc = (logits.argmax(-1) == labels).mean()
        return mean, {"loss": mean, "accuracy": acc}
    return loss


def node(model: dict):
    """``(init_fn, loss_fn, eval_fn)`` that the program is handed."""
    net = reference.arch(model)
    init = jax.jit(functools.partial(reference.init_arch, arch=net))
    loss = loss_fn(net)
    return init, loss, loss


def batcher(train, parts, traffic: dict, stream_seed: int):
    """The program's ``DeviceDataStream`` over the device-made train set."""
    from repro.data import DeviceDataStream

    return DeviceDataStream(SimpleNamespace(**train), parts,
                            traffic["batch"], seed=stream_seed)
