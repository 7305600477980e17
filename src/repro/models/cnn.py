"""Paper-faithful CNNs for the accuracy experiments (Table I / Fig. 3-7).

The Morph paper trains small CNNs on CIFAR-10 / FEMNIST via DecentralizePy;
the standard models there are GN-LeNet variants: two conv+groupnorm+pool
stages followed by a classifier head.  Pure-functional JAX, pytree params —
so the same model stacks on a node axis and flows through
``repro.core`` mixing exactly like the large architectures.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _conv_init(key, shape, dtype=jnp.float32):
    # shape = (h, w, c_in, c_out); He fan-in init.  Sampled in f32 and
    # cast, so any storage dtype holds the same (rounded) draw — bf16
    # params are exactly the f32 params rounded, never a different
    # random stream.
    fan_in = shape[0] * shape[1] * shape[2]
    std = math.sqrt(2.0 / fan_in)
    w = jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                    jnp.float32) * std
    return w.astype(dtype)


def cnn_params(key, *, in_channels: int = 3, num_classes: int = 10,
               image_size: int = 32, width: int = 32,
               dtype=jnp.float32) -> Dict:
    """GN-LeNet: conv5x5(w) -> GN -> pool -> conv5x5(2w) -> GN -> pool ->
    fc(num_classes).  ``dtype`` is the storage dtype of every leaf (the
    engines' bf16 exchange paths build bf16 models here)."""
    k1, k2, k3 = jax.random.split(key, 3)
    w2 = 2 * width
    feat = (image_size // 4) ** 2 * w2
    return {
        "conv1": {"w": _conv_init(k1, (5, 5, in_channels, width), dtype),
                  "b": jnp.zeros((width,), dtype)},
        "gn1": {"scale": jnp.ones((width,), dtype),
                "bias": jnp.zeros((width,), dtype)},
        "conv2": {"w": _conv_init(k2, (5, 5, width, w2), dtype),
                  "b": jnp.zeros((w2,), dtype)},
        "gn2": {"scale": jnp.ones((w2,), dtype),
                "bias": jnp.zeros((w2,), dtype)},
        "fc": {"w": (jax.random.truncated_normal(
            k3, -2.0, 2.0, (feat, num_classes), jnp.float32)
            / math.sqrt(feat)).astype(dtype),
            "b": jnp.zeros((num_classes,), dtype)},
    }


def _group_norm(p, x, groups: int = 2, eps: float = 1e-5):
    b, h, w, c = x.shape
    if c % groups:
        raise ValueError(
            f"group norm needs the channel count divisible by the group "
            f"count: got {c} channels, {groups} groups (pick a CNN width "
            f"that {groups} divides)")
    xg = x.reshape(b, h, w, groups, c // groups)
    mu = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = xg.var(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) * jax.lax.rsqrt(var + eps)
    return xg.reshape(b, h, w, c) * p["scale"] + p["bias"]


def _conv(p, x):
    y = jax.lax.conv_general_dilated(
        x, p["w"], window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"]


def _max_pool(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def _pool(x):
    """2x2 / stride-2 / VALID max-pool of ``x [b, H, W, C]``.

    The forward is ``lax.reduce_window(max)``.  The gradient goes to each
    window's *first* maximum in row-major order over (h, w), and 0 to the
    rest and to the row and column that VALID drops at an odd H or W:
    the tie rule of XLA's ``select`` with ``ge``, which ``reduce_window``'s
    own gradient uses, so ``dx`` is bitwise the same.  It is built from
    slices and ``where`` instead of that gradient's
    ``select_and_scatter_add``, which re-reads ``x`` in a memory-bound pass
    of its own; the forward keeps only the window choice (int8).
    """
    return _first_max_pool(x, x.shape[1], x.shape[2])


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _first_max_pool(x, h, w):
    return _max_pool(x)


def _window_choice(x):
    """int8 ``[b, H//2, W//2, C]``: the row-major slot (0-3) of each
    window's first maximum, by the chain of XLA's ``select`` with ``ge``
    (a later element takes the slot only where the held one is not
    ``>=`` it; NaN ordering included)."""
    b, h, w, c = x.shape
    ho, wo = h // 2, w // 2
    xr = x[:, :2 * ho, :2 * wo].reshape(b, ho, 2, wo, 2, c)
    best = xr[:, :, 0, :, 0]
    choice = jnp.zeros(best.shape, jnp.int8)
    for slot, (i, j) in enumerate(((0, 1), (1, 0), (1, 1)), 1):
        cand = xr[:, :, i, :, j]
        take = ~(best >= cand)
        best = jnp.where(take, cand, best)
        choice = jnp.where(take, jnp.int8(slot), choice)
    return choice


def _first_max_pool_fwd(x, h, w):
    return _max_pool(x), _window_choice(x)


# The row-major slot of each element of a window, laid out as the
# windows of ``x.reshape(b, H//2, 2, W//2, 2, C)``.  A constant, not an
# iota: built from an iota, XLA:CPU compiled the backward differently
# in a one-round superstep than in longer ones, and the engines'
# trajectories stopped being bitwise equal across superstep lengths.
_WINDOW_SLOT = np.arange(4, dtype=np.int8).reshape(1, 1, 2, 1, 2, 1)


def _first_max_pool_bwd(h, w, choice, g):
    b, ho, wo, c = g.shape
    dx = jnp.where(choice[:, :, None, :, None] == _WINDOW_SLOT,
                   g[:, :, None, :, None], jnp.zeros((), g.dtype))
    dx = dx.reshape(b, 2 * ho, 2 * wo, c)
    return (jnp.pad(dx, ((0, 0), (0, h - 2 * ho), (0, w - 2 * wo), (0, 0))),)


_first_max_pool.defvjp(_first_max_pool_fwd, _first_max_pool_bwd)


def cnn_forward(p, images: jax.Array) -> jax.Array:
    """images: [b, H, W, C] float -> logits [b, num_classes]."""
    x = jax.nn.relu(_group_norm(p["gn1"], _conv(p["conv1"], images)))
    x = _pool(x)
    x = jax.nn.relu(_group_norm(p["gn2"], _conv(p["conv2"], x)))
    x = _pool(x)
    x = x.reshape(x.shape[0], -1)
    return x @ p["fc"]["w"] + p["fc"]["b"]


def cnn_loss(p, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    logits = cnn_forward(p, batch["images"])
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    loss = nll.mean()
    acc = (logits.argmax(-1) == labels).mean()
    return loss, {"loss": loss, "accuracy": acc}


def cnn_accuracy(p, images, labels) -> jax.Array:
    return (cnn_forward(p, images).argmax(-1) == labels).mean()
