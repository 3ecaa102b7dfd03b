#!/usr/bin/env python3
"""Settle a trained cell's `correct` on the chip for any family: many seeds in
ONE process (`prove_train.py` for the driver the cell's mix names).

    python3 benchmark/prove_family.py --workload train_kimi_linear_ep32_s8k \
        --seeds 1,2,...  [--controls fp8,bf16_state \
                          --faults half_batch,no_routed --upper-seeds 2]

Per seed: the JAXJob through its followed steps (no window), then the plain
reference through the same steps, then the numbers compared. For the first
`--upper-seeds` seeds also each control (the reference in a lower precision,
in the program's place) and each planted fault (the reference with the
fault, in the program's place). This process holds the chip itself; the
benchmark's own runs never come here.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--upper-seeds", type=int, default=2)
    ap.add_argument("--leaves", type=int, default=0,
                    help="also print the N worst leaves of each reading")
    ap.add_argument("--no-chip", action="store_true")
    ap.add_argument("--toy")
    a = ap.parse_args()
    seeds = [int(s) for s in a.seeds.split(",")]
    args = argparse.Namespace(workload=a.workload, seed=seeds[0], seconds=0.0,
                              trace=0, no_chip=a.no_chip, toy=a.toy)
    from lib import harness

    for k, v in harness.child_env(args).items():
        os.environ.setdefault(k, v)
    from lib import child as child_lib

    cell = harness.load_cell(args)
    drv = importlib.import_module(f"drivers.{cell.driver_name}")
    ctx = child_lib.Context(cell, args, None)
    cfg, mix = cell.config, cell.traffic
    leaf_of = drv.reference_of(cfg).leaf_of

    def as_program(out: dict, like: dict) -> dict:
        """A reference's readings in the program's place, leaf for leaf."""
        back = {leaf_of(k): k for k in like["first_grad"]}
        return {"loss": out["loss"], "grad_norm": out["grad_norm"],
                "first_grad": {back[k]: v
                               for k, v in out["first_grad"].items()},
                "change": {back[k]: v for k, v in out["change"].items()}}

    worst_leaves = lambda mine, ref_out: drv.worst_leaves(
        mine, ref_out, leaf_of, a.leaves)

    try:
        for i, raw in enumerate(seeds):
            seed = harness.weight_seed(raw)
            t = time.monotonic()
            job = drv.run_job(ctx, seed, 0.0, False)
            t_job = time.monotonic() - t
            rows = drv.loader_rows(job["corpus"], seed, mix["batch_size"],
                                   mix["seq_len"], int(mix["follow_steps"]))
            t = time.monotonic()
            ref_out = drv.reference_steps(cfg, seed, rows, mix["optimizer"],
                                          ctx.devices)
            t_ref = time.monotonic() - t
            prog = job["program"]
            row = {"seed": raw, "program": drv.compare(prog, ref_out, leaf_of),
                   "counters": {k: prog[k] for k in mix.get("counters", [])},
                   "job_s": round(t_job, 1), "reference_s": round(t_ref, 1),
                   "memory_peak_bytes": job["memory_peak_bytes"],
                   "loss": prog["loss"], "ref_loss": ref_out["loss"],
                   "grad_norm": prog["grad_norm"],
                   "ref_grad_norm": ref_out["grad_norm"]}
            if a.leaves:
                row["leaves"] = worst_leaves(prog, ref_out)
            print("seed", json.dumps(row), flush=True)
            if i < a.upper_seeds:
                for kind, names in (("lower", a.controls), ("fault", a.faults)):
                    for name in [n for n in names.split(",") if n]:
                        bad = drv.reference_steps(
                            cfg, seed, rows, mix["optimizer"], ctx.devices,
                            **{kind: name})
                        bad = as_program(bad, prog)
                        line = {"seed": raw, f"{kind}_{name}": drv.compare(
                            bad, ref_out, leaf_of)}
                        if a.leaves:
                            line["leaves"] = worst_leaves(bad, ref_out)
                        print("seed", json.dumps(line), flush=True)
            ctx.free_device()
    finally:
        ctx.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
