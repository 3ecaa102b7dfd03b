"""Engine step: first token to finish over the tokens after the first
(`usage.decode_ms`), median over the requests."""

from lib import stats


def read(run):
    vals = [r["usage"]["decode_ms"] / (len(r["token_ids"]) - 1)
            for r in run["requests"]
            if r.get("usage") and r["usage"].get("decode_ms") is not None
            and len(r["token_ids"]) > 1]
    return stats.percentile(vals, 50)
