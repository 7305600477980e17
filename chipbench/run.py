"""The chip benchmark's one command: one run of one cell.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout that holds the program (``src/repro``) and
``BENCHMARK.json``, on a machine whose JAX sees a TPU with at least the
chips the cell asks for; otherwise it exits nonzero and prints no
result.  The last line of standard output is the result object; the
numbers that decided ``correct``, each beside its limit, are the last
lines of standard error.  The persistent compilation cache is
``<checkout>/.jax_cache``, so only a cell's first run in a checkout
compiles.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chipbench: no program at {ROOT / 'src' / 'repro'}; "
              "nothing was run", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from chipbench import harness
    cell = harness.load_cell(args.workload)

    import jax
    harness.use_checkout_cache(ROOT)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"chipbench: {args.workload} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devs)} {devs[0].platform} "
              "device(s); nothing was run", file=sys.stderr)
        return 2
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T0)
    lines = result.pop("_lines")
    print(json.dumps(result), flush=True)
    print("\n".join(lines), file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
