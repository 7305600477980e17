"""The CNN family's reference half: the node model of a configuration's
layer list in plain ``jax.numpy``, every contraction at
``Precision.HIGHEST`` in float32 (the default precision in the bfloat16
control), and the batch the program's device data stream draws.

It imports nothing of the program under test.  :func:`init_node` is also
the program's ``init_fn`` (``families/cnn.py``), so both sides start from
the same seeded weights.
"""
from __future__ import annotations

import collections
import math

import jax
import jax.numpy as jnp

from chipbench.reference import leaf_dtype, precision

GN_EPS = 1e-5


def arch(model: dict) -> tuple:
    """The node model of a configuration as a hashable (static) value:
    ``(image_size, in_channels, layers)``."""
    return (model["image_size"], model["in_channels"],
            tuple(tuple(layer) for layer in model["layers"]))


def param_layers(arch: tuple) -> list:
    """``[(name, kind, shapes)]`` for each layer of the architecture
    that holds weights, in order, with the shapes of its leaves: a conv
    ``["conv", out, k]`` (``k x k``, SAME, with bias) is ``conv<i>``, a
    ``["group_norm", groups]`` ``gn<i>``, a ``["dense", out]`` ``fc<i>``.
    ``relu``, ``pool`` (2x2 max, stride 2) and ``flatten`` hold none."""
    h, c, layers = arch
    feat, out, seen = None, [], collections.Counter()
    for layer in layers:
        kind = layer[0]
        if kind == "conv":
            cout, k = layer[1], layer[2]
            seen["conv"] += 1
            out.append((f"conv{seen['conv']}", kind,
                        {"w": (k, k, c, cout), "b": (cout,)}))
            c = cout
        elif kind == "group_norm":
            seen["gn"] += 1
            out.append((f"gn{seen['gn']}", kind,
                        {"scale": (c,), "bias": (c,)}))
        elif kind == "pool":
            h //= 2
        elif kind == "flatten":
            feat = h * h * c
        elif kind == "dense":
            seen["fc"] += 1
            out.append((f"fc{seen['fc']}", kind,
                        {"w": (feat, layer[1]), "b": (layer[1],)}))
            feat = layer[1]
        elif kind != "relu":
            raise ValueError(f"unknown layer {layer!r}")
    return out


def init_arch(key, arch: tuple):
    """One node's weights: He-scaled truncated normals for the convs,
    ``1/sqrt(fan_in)`` for the linear layers, zero biases, unit GroupNorm
    scales."""
    layers = param_layers(arch)
    keys = jax.random.split(key, len(layers))
    params = {}
    for k, (name, kind, shapes) in zip(keys, layers):
        if kind == "group_norm":
            params[name] = {"scale": jnp.ones(shapes["scale"], jnp.float32),
                            "bias": jnp.zeros(shapes["bias"], jnp.float32)}
            continue
        w = shapes["w"]
        fan_in = math.prod(w[:-1])
        std = math.sqrt((2.0 if kind == "conv" else 1.0) / fan_in)
        params[name] = {"w": jax.random.truncated_normal(
            k, -2.0, 2.0, w, jnp.float32) * std,
            "b": jnp.zeros(shapes["b"], jnp.float32)}
    return params


def init_node(key, model: dict):
    """One node's seeded weights (:func:`init_arch`)."""
    return init_arch(key, arch(model))


def forward(p, x, arch: tuple):
    """Logits ``[b, classes]`` of images ``x [b, H, W, C]`` through the
    layers of ``arch``; the dtype of ``p`` sets the arithmetic."""
    prec = precision(leaf_dtype(p))
    names = iter(name for name, *_ in param_layers(arch))
    h = x
    for layer in arch[2]:
        kind = layer[0]
        if kind == "conv":
            q = p[next(names)]
            h = jax.lax.conv_general_dilated(
                h, q["w"], (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=prec) + q["b"]
        elif kind == "group_norm":
            q, groups = p[next(names)], layer[1]
            b, hh, ww, c = h.shape
            g = h.reshape(b, hh, ww, groups, c // groups)
            mu = g.mean(axis=(1, 2, 4), keepdims=True)
            var = ((g - mu) ** 2).mean(axis=(1, 2, 4), keepdims=True)
            g = (g - mu) / jnp.sqrt(var + GN_EPS)
            h = g.reshape(b, hh, ww, c) * q["scale"] + q["bias"]
        elif kind == "relu":
            h = jnp.maximum(h, 0)
        elif kind == "pool":
            h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        elif kind == "flatten":
            h = h.reshape(h.shape[0], -1)
        else:
            q = p[next(names)]
            h = jnp.dot(h, q["w"], precision=prec) + q["b"]
    return h


def loss_and_accuracy(p, batch, model: dict):
    """Mean cross-entropy of ``batch`` (``images``, ``labels``) and the
    share of its images classified correctly."""
    logits = forward(p, batch["images"].astype(leaf_dtype(p)), arch(model))
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    return nll.mean(), (logits.argmax(-1) == labels).sum() / labels.shape[0]


def draw(train, rows, sizes, key, rnd, batch: int):
    """Every node's round-``rnd`` batch ``[n, batch, ...]``, as the
    program's device data stream draws it: node ``i`` takes ``batch``
    rows uniformly, with replacement, from the first ``sizes[i]`` entries
    of its row table ``rows[i]``, by
    ``randint(fold_in(fold_in(key, rnd), i))``."""
    k_round = jax.random.fold_in(key, rnd)

    def one(table, size, i):
        take = jax.random.randint(jax.random.fold_in(k_round, i),
                                  (batch,), 0, size)
        sel = table[take]
        return {"images": train["images"][sel],
                "labels": train["labels"][sel]}

    return jax.vmap(one)(rows, sizes, jnp.arange(rows.shape[0]))
