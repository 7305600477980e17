#!/usr/bin/env python
"""Smoke-check README.md: every command in a fenced ``bash`` block must
run (exit 0) as written.

A small skip table of regular expressions exempts commands that mutate
the environment (``pip install``), re-run entire CI jobs (tier-1
``pytest``, the full ``benchmarks.run`` sweeps — their sections are
exercised individually), need a TPU, or would recurse into this script.
Skips are printed with their reason so the README can't silently rot
behind them.

Usage:  python tools/smoke_readme.py [--timeout SECONDS] [README.md]
Exit status: number of failing commands (capped at 125).
"""
from __future__ import annotations

import argparse
import re
import subprocess
import sys
import time
from pathlib import Path

SKIP = [
    ("pip install", "mutates the environment"),
    ("-m pytest", "covered by the tier-1 CI job"),
    ("-m benchmarks.run", "full sweep; sections run individually in CI"),
    ("python examples/", "smoke-run at tiny scale by "
                         "tools/run_examples.py (docs CI job)"),
    ("-m repro.tune", "retuning run; the committed cache is the "
                      "artifact under test"),
    ("check_bench.py", "needs a fresh bench_out; exercised by the "
                       "perf CI job"),
    ("smoke_readme", "would recurse"),
    (r"^python chip_smoke\.py", "needs a TPU and exits nonzero without "
                                "one; its CPU rehearsal runs here"),
]


def bash_commands(text: str) -> list:
    """Command lines from every ```bash fenced block (comments and blank
    lines dropped, continuation lines joined)."""
    cmds = []
    for block in re.findall(r"```bash\n(.*?)```", text, re.S):
        joined = block.replace("\\\n", " ")
        for line in joined.splitlines():
            line = line.strip()
            if line and not line.startswith("#"):
                cmds.append(line)
    return cmds


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("readme", nargs="?", default="README.md")
    ap.add_argument("--timeout", type=float, default=900.0)
    args = ap.parse_args(argv)

    root = Path(args.readme).resolve().parent
    cmds = bash_commands(Path(args.readme).read_text())
    if not cmds:
        print("no bash commands found in README", file=sys.stderr)
        return 1

    failures = 0
    for cmd in cmds:
        reason = next((why for pat, why in SKIP if re.search(pat, cmd)),
                      None)
        if reason:
            print(f"SKIP  {cmd}   [{reason}]")
            continue
        t0 = time.time()
        try:
            proc = subprocess.run(cmd, shell=True, cwd=root,
                                  capture_output=True, text=True,
                                  timeout=args.timeout)
            ok = proc.returncode == 0
        except subprocess.TimeoutExpired:
            ok, proc = False, None
        dt = time.time() - t0
        if ok:
            print(f"OK    {cmd}   [{dt:.0f}s]")
        else:
            failures += 1
            print(f"FAIL  {cmd}   [{dt:.0f}s]")
            if proc is not None:
                sys.stderr.write(proc.stdout[-2000:] + proc.stderr[-2000:])
            else:
                sys.stderr.write(f"  timed out after {args.timeout}s\n")
    if failures:
        print(f"\n{failures} README command(s) failed", file=sys.stderr)
    else:
        print("\nREADME commands: OK")
    return min(failures, 125)


if __name__ == "__main__":
    sys.exit(main())
