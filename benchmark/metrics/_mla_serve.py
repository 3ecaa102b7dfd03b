"""What the readers of the served latent attention share: how many live
rows the decode kernel covered in the traced part of the window, from the
engine's running count `mla_context_tokens` (the live rows over the slots
and the layers). The count at the window's open is `counters.before`; after
it, each request's `usage.counters` holds the count as the request found it
at its finish; the count at the traced part's end is interpolated between
the finishes either side of it. A program without the counter (an older
commit, another family) gives nothing to read."""


def traced_rows(run, key="mla_context_tokens"):
    trace = run.get("trace") or {}
    start = ((run.get("counters") or {}).get("before") or {}).get(key)
    if not trace.get("window_s") or start is None:
        return None
    lo = run["window"]["t_open"]
    hi = lo + trace["window_s"]
    snaps = [(lo, start)]
    for r in run.get("requests") or []:
        c = (r.get("usage") or {}).get("counters") or {}
        if (c.get(key) is not None and r.get("done") is not None
                and r["done"] > lo):
            snaps.append((r["done"], c[key]))
    snaps.sort()
    after = next((i for i, (t, _) in enumerate(snaps) if t >= hi), None)
    if not after:
        return None
    (t0, c0), (t1, c1) = snaps[after - 1], snaps[after]
    rows = c0 + (c1 - c0) * (hi - t0) / (t1 - t0) - start
    return rows if rows > 0 else None
