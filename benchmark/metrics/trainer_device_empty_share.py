"""Trainer: the share of a step in which nothing was dispatched and
unfetched, from the fetch of the step's scalars to the next step_fn call
(the record's `device_empty_ms` over `step_time_s`), median over the
window's steps."""

from lib import stats


def read(run):
    return stats.percentile(
        [100.0 * r["device_empty_ms"] / (r["step_time_s"] * 1e3)
         for r in run.get("steps") or []
         if "device_empty_ms" in r and r.get("step_time_s")], 50)
