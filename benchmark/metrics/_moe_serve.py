"""What the readers of the served expert layer's counters share. The
engine's metrics() of a family with routed experts carry, summed over its
sparse layers and decode steps: `moe_assignments` (rows through the
experts), `moe_expert_visits` (distinct experts that took a row, a layer
and step at a time), `moe_rows_dropped`. A program without them (an older
commit, another family) gives every reader here nothing to read."""


def growth(run, key):
    """A counter's growth between the window's two readings, or None."""
    c = run.get("counters") or {}
    a, b = (c.get("before") or {}).get(key), (c.get("after") or {}).get(key)
    if a is None or b is None or b <= a:
        return None
    return b - a


def decode_rows(cfg):
    """Assignments of one decode step in one sparse layer."""
    return (cfg["system"]["config"]["n_slots"]
            * cfg["num_experts_per_tok"])


def _snapshots(run, lo, hi):
    """(visits, assignments) as each request that finished inside
    [lo, hi] found them: `usage.counters` is the engine's running count at
    the request's finish, so the requests of a window are readings of it
    spread over the window."""
    out = []
    for r in run.get("requests") or []:
        c = (r.get("usage") or {}).get("counters")
        if c and r.get("done") is not None and lo <= r["done"] <= hi:
            out.append((r["done"], c.get("moe_expert_visits"),
                        c.get("moe_assignments")))
    return sorted(s for s in out if s[1] is not None and s[2] is not None)


def touched_per_decode_call(run, traced=False):
    """Mean distinct experts a decode step touched in one sparse layer:
    over the whole run (the window's two readings of the counters), or,
    `traced`, over the traced part of the window alone (between the first
    and the last request that finished inside it: a trace's calls have to
    be counted with THEIR occupancy, which at the window's opening is
    below the run's)."""
    if traced:
        lo = run["window"]["t_open"]
        snaps = _snapshots(run, lo, lo + run["trace"]["window_s"])
        if len(snaps) < 2 or snaps[-1][2] <= snaps[0][2]:
            return None
        visits = snaps[-1][1] - snaps[0][1]
        rows = snaps[-1][2] - snaps[0][2]
    else:
        visits = growth(run, "moe_expert_visits")
        rows = growth(run, "moe_assignments")
    if not visits or not rows:
        return None
    return visits * decode_rows(run["config"]) / rows
