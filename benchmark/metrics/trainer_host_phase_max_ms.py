"""Trainer: the longest single occurrence of a loop phase that is not a
fetch (the record's `host_phase_max_ms`), the maximum over the window's
records: an ordinary run reads a dispatch or a log line, a stalled loop the
stall."""


def read(run):
    vals = [r["host_phase_max_ms"] for r in run.get("steps") or []
            if "host_phase_max_ms" in r]
    return float(max(vals)) if vals else None
