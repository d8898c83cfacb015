"""Mean host time of the action-time lookup (span ``flame.pda.times``), per
lookup: window delta of ``times_s`` over ``times_n``.  None where the
program has no such counter or the window made no lookup."""


def read(rec):
    c = rec["counters"]
    if "times_s" not in c or not c.get("times_n"):
        return None
    return 1e3 * c["times_s"] / c["times_n"]
