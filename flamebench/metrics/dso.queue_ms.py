"""Mean wait of a chunk in the DSO's coalescing queue, from its enqueue to
the flush of its dispatch: window delta of ``dso_queue_delay_s`` over
``dso_queue_delay_n``.  None where the program has no such counters or the
window dispatched nothing."""


def read(rec):
    c = rec["counters"]
    if "dso_queue_delay_s" not in c or not c.get("dso_queue_delay_n"):
        return None
    return 1e3 * c["dso_queue_delay_s"] / c["dso_queue_delay_n"]
