"""Faults planted in the program under test, to show that ``correct``
catches them.

Each is a context manager that breaks the timed path underneath the
benchmark, the way a faulty optimisation would, for engines built while
it is active:

``unchanged``
    the local step returns the node models and optimizer state unchanged;
``half_batch``
    the local step trains on the first half of each node's batch, the
    mean taken over that half;
``node_altered``
    the local step's output for the first node
    is replaced by the second node's.
"""
from __future__ import annotations

import contextlib

FAULTS = ("unchanged", "half_batch", "node_altered")


@contextlib.contextmanager
def planted(name: str):
    import jax
    import repro.dlrt.compiled as engine

    if name not in FAULTS:
        raise ValueError(f"unknown fault {name!r}; one of {FAULTS}")
    make_step = engine.make_local_step

    def faulty_make_step(loss_fn, optimizer):
        step = make_step(loss_fn, optimizer)

        def faulty(params, opt_state, batch):
            if name == "unchanged":
                return params, opt_state
            if name == "half_batch":
                batch = jax.tree_util.tree_map(
                    lambda x: x[:, :x.shape[1] // 2], batch)
                return step(params, opt_state, batch)
            params, opt_state = step(params, opt_state, batch)
            return jax.tree_util.tree_map(
                lambda x: x.at[0].set(x[1]), params), opt_state
        return faulty

    engine.make_local_step = faulty_make_step
    try:
        yield
    finally:
        engine.make_local_step = make_step
