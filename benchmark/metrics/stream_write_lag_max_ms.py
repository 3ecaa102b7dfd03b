"""Router and server: the longest any token waited from its append on the
engine thread to its stream thread picking it up
(`usage.stream_write_lag_max_ms`), the MAXIMUM over the window's requests: a
stall of the stream threads touches the requests in flight, a few of
hundreds, and a percentile would hide it."""

from metrics._serve import usage_values


def read(run):
    vals = usage_values(run, "stream_write_lag_max_ms")
    return float(max(vals)) if vals else None
