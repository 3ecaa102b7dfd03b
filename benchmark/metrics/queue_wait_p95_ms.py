"""Scheduler: submit to the prefill leaving the queue (engine clock, from
`usage` under config.usage_timing), 95th percentile over the requests."""

from metrics._serve import usage_percentile


def read(run):
    return usage_percentile(run, "queue_wait_ms", 95)
