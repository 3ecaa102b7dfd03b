"""Engine step: queue exit to first token (`usage.prefill_ms`), median."""

from metrics._serve import usage_percentile


def read(run):
    return usage_percentile(run, "prefill_ms", 50)
