"""What the readers of the phase clock's host-time split share (PR 36). A
program built from this repo reports, beside each phase's wall, WHO had the
time (kubeflow_tpu/obs/trace.py, `PhaseClock`):

    usage.engine.cpu_ms = {"<phase>": ms}     the engine thread's own CPU
    usage.engine.device_empty_by_phase_ms = {"<phase>": ms}
        `device_empty_ms` (nothing dispatched and unfetched) split by the
        phase the engine thread was in; sums to it
    usage.stream_write_lag_max_ms             the stream thread, after the engine

and the Trainer's record, per step: `device_empty_ms` (a fetch -> the next
step_fn call), `host_phase_max_ms` (the longest single phase occurrence that
is not a fetch). A program without these keys (the parent of the PR that
added them) sends none: every reader here then finds nothing and returns
None."""

from lib import stats
from metrics._engine import engine_usages, phase_ms

#: the engine phases that never wait for the device or the runtime's queue:
#: wall less CPU there is the interpreter's or the OS's
HOST_ONLY = ("sched", "prefill_pack", "decode_plan", "replay")


def device_empty_share(run, *phases):
    """Percent of `usage.decode_ms` with the device empty while the engine
    thread was in one of `phases`; median over the requests."""
    return stats.percentile(
        [100.0 * sum(e["device_empty_by_phase_ms"].get(p, 0.0)
                     for p in phases) / u["decode_ms"]
         for u, e in engine_usages(run)
         if "device_empty_by_phase_ms" in e], 50)


def offcpu_share(run, *phases):
    """Percent of the wall of `phases` that the engine thread spent off the
    CPU; median over the requests that saw any of them."""
    vals = []
    for _, e in engine_usages(run):
        wall = phase_ms(e, *phases)
        if "cpu_ms" in e and wall > 0:
            cpu = sum(e["cpu_ms"].get(p, 0.0) for p in phases)
            vals.append(100.0 * (wall - cpu) / wall)
    return stats.percentile(vals, 50)

