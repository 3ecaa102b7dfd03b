"""What the served cells' readers share: the `usage` fields of the
requests that finished, and the time to first token at the client."""

from lib import stats


def usage_values(run, key):
    return [r["usage"][key] for r in run["requests"]
            if r.get("usage") and r["usage"].get(key) is not None]


def usage_percentile(run, key, q):
    return stats.percentile(usage_values(run, key), q)


def ttft_percentile(run, q):
    """First token received minus instant DUE, over all requests due in
    the window that got a token."""
    return stats.percentile([(r["token_at"][0] - r["due"]) * 1e3
                             for r in run["requests"] if r["token_at"]], q)
