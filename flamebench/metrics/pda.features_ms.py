"""Mean host time of the PDA side-feature query on a pool miss (span
``flame.pda.features``): window delta of ``features_s`` over
``features_n``.  None where the program has no such counter or the window
made no query."""


def read(rec):
    c = rec["counters"]
    if "features_s" not in c or not c.get("features_n"):
        return None
    return 1e3 * c["features_s"] / c["features_n"]
