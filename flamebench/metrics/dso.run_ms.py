"""Executor time per dispatch as the host sees it: the launch (span
``flame.dso.launch``) plus the wait for its outputs, window delta of
``dso_launch_s`` + ``dso_wait_s`` over ``dso_dispatches``.  None where the
program has no such counters or the window dispatched nothing."""

KEYS = ("dso_launch_s", "dso_wait_s")


def read(rec):
    c = rec["counters"]
    if any(k not in c for k in KEYS) or not c.get("dso_dispatches"):
        return None
    return 1e3 * sum(c[k] for k in KEYS) / c["dso_dispatches"]
