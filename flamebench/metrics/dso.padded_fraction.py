"""Share of the ``cached`` family's dispatched candidate slots that carried
padding, over the window (DSO slot counters, window delta)."""


def read(rec):
    c = rec["counters"]
    slots = sum(v for k, v in c.items() if k.startswith("slots_cached_b"))
    valid = sum(v for k, v in c.items() if k.startswith("valid_cached_b"))
    return 100.0 * (1.0 - valid / slots) if slots > 0 else None
