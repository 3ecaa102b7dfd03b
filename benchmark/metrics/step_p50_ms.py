"""Trainer: median of the job's own per-step interval (metrics file,
`step_time_s`, closed by device_get) over the steps of the window."""

from lib import stats


def read(run):
    return stats.percentile([r["step_time_s"] * 1e3 for r in run["steps"]],
                            50)
