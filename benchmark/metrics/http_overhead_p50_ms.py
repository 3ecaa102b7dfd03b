"""Router and server: the client's time from sending to the first token,
less what the engine accounts for (queue wait + prefill); median."""

from lib import stats


def read(run):
    vals = []
    for r in run["requests"]:
        u = r.get("usage") or {}
        if (r["token_at"] and u.get("queue_wait_ms") is not None
                and u.get("prefill_ms") is not None):
            vals.append((r["token_at"][0] - r["sent"]) * 1e3
                        - u["queue_wait_ms"] - u["prefill_ms"])
    return stats.percentile(vals, 50)
