"""End-to-end decentralized training launcher.

Trains a population of DL nodes on synthetic non-IID data with the full
in-graph Morph controller (similarity -> Gumbel-top-k selection ->
matching -> mixing, all inside one jitted superstep).

CPU quickstart (reduced arch, a few hundred rounds):
  python -m repro.launch.train --arch llama3.2-3b --reduced \\
      --nodes 8 --rounds 200 --batch 8 --seq 128

On a TPU pod the same script runs the full config under the production
mesh (--mesh single|multi) with the sharding policies of DESIGN.md §4.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..data import make_token_stream
from ..data.pipeline import TokenBatcher
from ..dlrt import (MorphHParams, init_train_state, make_train_step,
                    train_state_sharding)
from ..optim import sgd
from .mesh import make_production_mesh


def build_batcher(args, cfg, node: int) -> TokenBatcher:
    # per-node Markov stream with node-specific transition structure ==
    # non-IID local distributions (each node sees different "dialect")
    toks = make_token_stream(args.stream_len, cfg.vocab_size,
                             seed=1000 + node,
                             concentration=0.05 + 0.1 * (node % 4))
    return TokenBatcher(toks, args.batch, args.seq, seed=node)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--reduced", action="store_true",
                    help="train the CPU smoke-scale variant")
    ap.add_argument("--nodes", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8,
                    help="per-node batch size")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--k", type=int, default=3, help="Morph in-degree")
    ap.add_argument("--view-size", type=int, default=5)
    ap.add_argument("--beta", type=float, default=500.0)
    ap.add_argument("--delta-r", type=int, default=5)
    ap.add_argument("--microbatch", type=int, default=None)
    ap.add_argument("--stream-len", type=int, default=200_000)
    ap.add_argument("--mesh", choices=("none", "single", "multi"),
                    default="none")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--checkpoint-dir", default=None)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    opt = sgd(args.lr)
    hp = MorphHParams(k=min(args.k, args.nodes - 1),
                      view_size=min(args.view_size, args.nodes - 1),
                      beta=args.beta)

    state = init_train_state(jax.random.PRNGKey(0), cfg, opt, args.nodes)
    step_topo = make_train_step(cfg, opt, hp, microbatch=args.microbatch,
                                do_topology=True)
    step_plain = make_train_step(cfg, opt, hp, microbatch=args.microbatch,
                                 do_topology=False)

    if args.mesh != "none":
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
        state_shape = jax.eval_shape(lambda s: s, state)
        sh = train_state_sharding(mesh, cfg, state_shape)
        with jax.set_mesh(mesh):
            state = jax.device_put(state, sh)
            step_topo = jax.jit(step_topo, in_shardings=(sh, None),
                                out_shardings=(sh, None))
            step_plain = jax.jit(step_plain, in_shardings=(sh, None),
                                 out_shardings=(sh, None))
    else:
        step_topo = jax.jit(step_topo)
        step_plain = jax.jit(step_plain)

    batchers = [build_batcher(args, cfg, i) for i in range(args.nodes)]
    ckpt = None
    if args.checkpoint_dir:
        from ..checkpoint import CheckpointManager
        ckpt = CheckpointManager(args.checkpoint_dir)

    t0 = time.time()
    for rnd in range(args.rounds):
        node_batches = [b.next() for b in batchers]
        stacked = {
            k: jnp.asarray(np.stack([nb[k] for nb in node_batches]))
            for k in ("tokens", "labels")}
        step = step_topo if rnd % args.delta_r == 0 else step_plain
        state, metrics = step(state, stacked)
        if rnd % args.log_every == 0 or rnd == args.rounds - 1:
            loss = float(metrics["loss"])
            deg = np.asarray(state.morph.edges.sum(1))
            print(f"round {rnd:5d}  loss {loss:.4f}  "
                  f"in-deg [{deg.min()}..{deg.max()}]  "
                  f"({time.time() - t0:.1f}s)", flush=True)
        if ckpt is not None and rnd and rnd % 100 == 0:
            ckpt.save(rnd, {"params": state.params})
    if ckpt is not None:
        ckpt.save(args.rounds, {"params": state.params})
    print(f"done: {args.rounds} rounds in {time.time() - t0:.1f}s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
