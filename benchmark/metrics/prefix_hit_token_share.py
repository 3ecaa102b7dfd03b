"""KV manager: prompt tokens served from the prefix cache over prompt
tokens sent (`usage.cached_tokens`), in percent. A mix with no shared
prefix reads 0 by design: the cache is looked up and never hits."""


def read(run):
    sent = cached = 0
    for r in run["requests"]:
        u = r.get("usage")
        if u and u.get("cached_tokens") is not None:
            sent += len(r["prompt"])
            cached += u["cached_tokens"]
    return 100.0 * cached / sent if sent else None
