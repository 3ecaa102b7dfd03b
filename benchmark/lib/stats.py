"""Metric arithmetic (copied in spirit from kubeflow_tpu/loadgen/slo.py:
numpy's percentile over all samples, nothing dropped)."""

from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float | None:
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))
