"""What every run does, whatever the cell: start the child, let the cell's
driver measure, read the per-layer metrics, print the numbers compared and
the one result line."""

from __future__ import annotations

import json
import os
import sys
import time

from lib import proto, spec

#: a run ends within 360 s; the first in a checkout, which compiles, 1200
RUN_DEADLINE_S = 1150.0


def log(*a) -> None:
    print("[bench]", *a, file=sys.stderr, flush=True)


def load_cell(args) -> spec.Cell:
    cell = spec.Cell(args.workload)
    if args.toy:   # CPU rehearsal: the same keys at toy sizes
        toy = spec.load_json(args.toy)
        _merge(cell.config, toy.get("config", {}))
        _merge(cell.traffic, toy.get("traffic", {}))
    return cell


def _merge(into: dict, frm: dict) -> None:
    for k, v in frm.items():
        if isinstance(v, dict) and isinstance(into.get(k), dict):
            _merge(into[k], v)
        else:
            into[k] = v


def child_env(args) -> dict[str, str]:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [spec.ROOT] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    if args.no_chip:   # XLA:CPU's persistent cache is not to be trusted
        return env
    # one compile cache at a fixed path inside the checkout, the one the
    # program's own rule (runtime/compile_cache.py) would choose, so that
    # only a checkout's first run of a cell compiles
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(spec.ROOT, ".jax_compile_cache"))
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    env.setdefault("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp
    return env


def start_child(args) -> proto.Child:
    argv = [sys.executable, os.path.join(spec.HERE, "run.py"),
            "--role", "child", "--workload", args.workload,
            "--seed", str(args.seed), "--seconds", str(args.seconds),
            "--trace", str(args.trace)]
    if args.no_chip:
        argv.append("--no-chip")
    if args.toy:
        argv += ["--toy", args.toy]
    return proto.Child(argv, child_env(args), spec.ROOT)


def weight_seed(seed: int) -> int:
    """The seed of the weights (and of the trainer's loader): --seed can
    exceed 32 signed bits, a PRNG key takes 31, and the loader reads 0 as
    'unseeded'."""
    return 1 + int(seed) % 2147483629


def judge(numbers: dict) -> bool:
    """Each number compared stands beside its limit; the run is correct
    when every one is inside it."""
    return bool(numbers) and all(
        n["value"] is not None and n["value"] <= n["limit"]
        for n in numbers.values())


def parent_main(args, t_start: float) -> int:
    cell = load_cell(args)
    child = start_child(args)
    try:
        hello = child.expect("hello", timeout=300)
        run = cell.driver().parent(cell, args, child, t_start,
                                   t_start + RUN_DEADLINE_S)
    except proto.ChildDied as e:
        log(f"FAILED: {e}")
        return 1
    finally:
        rc = child.close()
    if run is None:
        return 1
    kind = hello["device"]["kind"]
    if args.no_chip:   # the CPU rehearsal has no peaks of its own
        kind = next(iter(cell.peaks_table["devices"]))
    run["peaks"] = cell.peaks(kind)
    run["cell"] = cell
    if args.trace:
        metrics = {}
        for m in cell.per_layer:
            value = spec.metric_reader(m["name"])(run)
            if value is not None:   # nothing to read: left out, never 0
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": run["end_to_end"][m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    device = dict(hello["device"],
                  memory_peak_bytes=run["memory_peak_bytes"])
    out = {"correct": judge(run["numbers"]),
           "attempted": run["attempted"], "failed": run["failed"],
           "metrics": metrics, "device": device}
    if args.trace:
        device.update(busy_s=run["trace"]["busy_s"],
                      window_s=run["trace"]["window_s"])
        out["breakdown"] = run["trace"]["breakdown"]
    out["numbers"] = run["numbers"]   # last: what was compared, and limits
    log(f"correct={out['correct']} child_exit={rc}")
    for name, n in run["numbers"].items():   # the last lines of stderr
        log(f"compared {name}: {n['value']} (limit {n['limit']})")
    print(json.dumps(out), flush=True)
    return 0


def child_main(args) -> int:
    from lib import child as child_lib

    cell = load_cell(args)
    link = proto.Link()
    try:
        fault = None
        if args.no_chip and os.environ.get("BENCH_TEST_FAULT"):
            # benchmark/tests only: break the timed path underneath and see
            # `correct` come out false
            import importlib.util

            fspec = importlib.util.spec_from_file_location(
                "bench_faults", os.path.join(spec.HERE, "tests", "faults.py"))
            faults = importlib.util.module_from_spec(fspec)
            fspec.loader.exec_module(faults)
            fault = faults.plant(os.environ["BENCH_TEST_FAULT"])
        ctx = child_lib.Context(cell, args, link)
        cell.driver().child(ctx, fault=fault)
    except child_lib.Refused as e:
        log(f"child: {e}")
        link.say("failed", why=str(e))
        return 1
    except BaseException as e:
        import traceback

        traceback.print_exc()
        link.say("failed", why=f"{type(e).__name__}: {e}"[-1500:])
        return 1
    return 0
