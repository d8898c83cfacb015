"""History-KV pool hits over hits plus misses, window delta of the pool's
counters (stale entries count as misses)."""


def read(rec):
    c = rec["counters"]
    h, m = c.get("pool_hits", 0.0), c.get("pool_misses", 0.0)
    return 100.0 * h / (h + m) if h + m > 0 else None
