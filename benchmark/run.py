#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process never imports JAX: it generates the load and takes the
end-to-end metrics by its own clock. One child (this file with --role
child) owns the cell's chips, brings the system up through `Platform`,
and after the window runs the plain reference. See benchmark/README.md.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

T_START = time.monotonic()   # set-up is counted from here
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # the child's own arguments; the rehearsal on the CPU (tests/) sets the
    # last two, the benchmark's command never does
    ap.add_argument("--role", choices=("parent", "child"), default="parent",
                    help=argparse.SUPPRESS)
    ap.add_argument("--no-chip", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--toy", help=argparse.SUPPRESS)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from lib import harness

    if args.role == "child":
        return harness.child_main(args)
    return harness.parent_main(args, T_START)


if __name__ == "__main__":
    sys.exit(main())
