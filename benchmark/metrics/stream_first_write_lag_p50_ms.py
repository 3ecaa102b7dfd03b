"""Router and server: from the engine's first token to the server thread's
first SSE chunk written (`usage.first_write_lag_ms`), median: the server's
half of the HTTP overhead that lies after the engine."""

from metrics._serve import usage_percentile


def read(run):
    return usage_percentile(run, "first_write_lag_ms", 50)
