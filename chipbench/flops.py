"""Model FLOPs of the benchmark's rounds, from shapes alone.

Only the work a round requires counts, as multiply-adds times two:

* Forward per sample: each convolution (``k x k``, SAME) over its input's
  H x W, each linear layer's ``in x out``.  GroupNorm, ReLU and pooling
  are not counted.
* Training per sample: the forward, the weight gradients (as many MACs
  again), and the input gradients of every layer but the first (nothing
  needs the gradient of the images).
* Eq. 3: one Gram ``2 n^2 D`` per negotiation round (not per refresh: the
  strategy reads the similarity only when it negotiates).
* Mixing: ``2 (edges + n) D`` per round, the edges in use that round plus
  each node's own model.
* Evaluation: every node's forward over the test set.
"""
from __future__ import annotations


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
    keyed ``conv<i>`` or ``fc<i>`` by
    its place among them."""
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


def eval_flops(model: dict, nodes: int, test: int) -> int:
    return 2 * sum(forward_macs(model).values()) * nodes * test


def round_flops(model: dict, nodes: int, batch: int, edges: int,
                negotiates: bool) -> int:
    """One round: every node's local step, the mixing over ``edges``
    in-edges, and the Eq.-3 Gram when the round negotiates."""
    d = param_count(model)
    gram = 2 * nodes * nodes * d if negotiates else 0
    return (nodes * batch * train_flops_per_sample(model)
            + 2 * (edges + nodes) * d + gram)

