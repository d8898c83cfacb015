"""Host time of the history-KV pool per lookup (spans ``flame.pool.lookup``
and ``flame.pool.put``): window delta of ``pool_lookup_s`` +
``pool_put_s`` over ``pool_lookup_n``.  None where the program has no such
counters or the window made no lookup."""


def read(rec):
    c = rec["counters"]
    if any(k not in c for k in ("pool_lookup_s", "pool_put_s")) \
            or not c.get("pool_lookup_n"):
        return None
    return 1e3 * (c["pool_lookup_s"] + c["pool_put_s"]) / c["pool_lookup_n"]
