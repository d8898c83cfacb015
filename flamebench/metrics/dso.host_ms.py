"""DSO host time per dispatch outside the executor (spans
``flame.dso.stack``, ``flame.dso.readback`` and ``flame.dso.scatter``):
window delta of ``dso_stack_s`` + ``dso_readback_s`` + ``dso_scatter_s``
over ``dso_dispatches``.  None where the program has no such counters or
the window dispatched nothing."""

KEYS = ("dso_stack_s", "dso_readback_s", "dso_scatter_s")


def read(rec):
    c = rec["counters"]
    if any(k not in c for k in KEYS) or not c.get("dso_dispatches"):
        return None
    return 1e3 * sum(c[k] for k in KEYS) / c["dso_dispatches"]
