"""Whole path, at the client: median of first token received minus instant
DUE over all requests due in the window (see ttft_p95_ms)."""

from metrics._serve import ttft_percentile


def read(run):
    return ttft_percentile(run, 50)
