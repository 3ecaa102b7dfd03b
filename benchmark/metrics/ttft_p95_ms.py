"""Whole path, at the client: 95th percentile over all requests due in the
window of first token received minus instant DUE. Unbounded: at four
fifths of the knee it amplifies every 1 % of capacity fivefold (1 / (1 -
load)) and swings +-9 % between runs of one schedule (PERF.md Findings)."""

from metrics._serve import ttft_percentile


def read(run):
    return ttft_percentile(run, 95)
