"""What the readers of `usage.engine` share. A server built from this
repo with `usage_timing` on reports, per finished request, what its engine
THREAD did from the request's first token to its finish
(kubeflow_tpu/obs/trace.py, `PhaseClock.usage`):

    "engine": {"phases": {"<phase>": [ms, count], ...},
               "device_empty_ms": ms,
               "phase_max_ms": ms, "phase_max": "<phase>"}

The phases partition that window, so their ms sum to `usage.decode_ms`. A
program without the clock (an older commit) sends no such key: every
reader here then finds nothing and returns None."""


def engine_usages(run):
    """(usage, usage["engine"]) of each request that carries the field and
    a decode window to set it against."""
    return [(r["usage"], r["usage"]["engine"]) for r in run["requests"]
            if r.get("usage") and r["usage"].get("engine")
            and r["usage"].get("decode_ms")]


def phase_ms(engine, *names):
    return sum(engine["phases"].get(n, (0.0, 0))[0] for n in names)


def phase_count(engine, name):
    return engine["phases"].get(name, (0.0, 0))[1]
