"""How late the load generator sent each request against its due instant:
a starved generator must not read as a fast server."""

from lib import stats


def read(run):
    late = [(r["sent"] - r["due"]) * 1e3 for r in run["requests"]
            if r["sent"] is not None]
    return stats.percentile(late, 95)
