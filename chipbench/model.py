"""The node model that the program trains, built from a configuration's
layer list.

A configuration (``chipbench/configs/<name>.json``) states its published
architecture as ``layers``, in order.  The program's model module
(``repro.models.cnn``) builds only its own two-stage CNN, so the
benchmark assembles the published one from that module's layer
functions: its SAME convolution, its GroupNorm and its 2x2 max-pool; a
linear layer is ``x @ w + b`` as the module writes its head.  The
parameter layout (``conv<i>``, ``gn<i>``, ``fc<i>``) and the seeded
start are the reference's (:func:`chipbench.reference.param_layers`,
:func:`chipbench.reference.init_node`), which imports nothing of the
program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import reference


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
