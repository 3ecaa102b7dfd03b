#!/usr/bin/env python3
"""Settle a trained cell's `correct` on the chip: many seeds in ONE process.

    python3 benchmark/prove_train.py --workload train_fsdp2tp2 \
        --seeds 1,2,...  [--control fp8 --faults half_batch,no_exchange --upper-seeds 3]

Per seed: the JAXJob through its followed steps (no window), then the plain
reference through the same steps, then the numbers compared. For the first
`--upper-seeds` seeds also the control (the reference in the lower
precision, in the program's place) and each planted fault (the reference
with the fault, in the program's place). This process holds the chips
itself; the benchmark's own runs never come here.
"""

from __future__ import annotations

import argparse
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
    ap.add_argument("--control")
    ap.add_argument("--faults", default="")
    ap.add_argument("--upper-seeds", type=int, default=3)
    ap.add_argument("--no-chip", action="store_true")
    ap.add_argument("--toy")
    a = ap.parse_args()
    seeds = [int(s) for s in a.seeds.split(",")]
    args = argparse.Namespace(workload=a.workload, seed=seeds[0], seconds=0.0,
                              trace=0, no_chip=a.no_chip, toy=a.toy)
    from lib import harness

    for k, v in harness.child_env(args).items():
        os.environ.setdefault(k, v)
    from drivers import jaxjob_window as drv
    from lib import child as child_lib

    cell = harness.load_cell(args)
    ctx = child_lib.Context(cell, args, None)
    cfg, mix = cell.config, cell.traffic
    back = {v: k for k, v in drv.LEAF.items()}

    def as_program(out: dict) -> dict:
        return {"loss": out["loss"], "grad_norm": out["grad_norm"],
                "first_grad": {back[k]: v
                               for k, v in out["first_grad"].items()},
                "change": {back[k]: v for k, v in out["change"].items()}}

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
            row = {"seed": raw, "program": drv.compare(job["program"],
                                                       ref_out),
                   "job_s": round(t_job, 1), "reference_s": round(t_ref, 1),
                   "memory_peak_bytes": job["memory_peak_bytes"],
                   "loss": job["program"]["loss"],
                   "ref_loss": ref_out["loss"],
                   "grad_norm": job["program"]["grad_norm"],
                   "ref_grad_norm": ref_out["grad_norm"]}
            if i < a.upper_seeds:
                if a.control:
                    ctl = drv.reference_steps(cfg, seed, rows,
                                              mix["optimizer"], ctx.devices,
                                              lower=a.control)
                    row["control_" + a.control] = drv.compare(
                        as_program(ctl), ref_out)
                for fault in [f for f in a.faults.split(",") if f]:
                    bad = drv.reference_steps(cfg, seed, rows,
                                              mix["optimizer"], ctx.devices,
                                              fault=fault)
                    row["fault_" + fault] = drv.compare(as_program(bad),
                                                        ref_out)
            ctx.free_device()
            print("seed", json.dumps(row), flush=True)
    finally:
        ctx.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
