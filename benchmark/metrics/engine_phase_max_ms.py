"""Engine step: the longest single non-idle phase occurrence any request
lived through, submit to finish (`usage.engine.phase_max_ms`), maximum over
the requests. An ordinary run reads about one prefill fetch; a stalled
engine thread reads the stall."""

from metrics._engine import engine_usages


def read(run):
    vals = [e["phase_max_ms"] for _, e in engine_usages(run)
            if e.get("phase_max_ms") is not None]
    return float(max(vals)) if vals else None
