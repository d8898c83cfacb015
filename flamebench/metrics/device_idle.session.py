"""Share of the traced window in which no operation ran on the device
(see ``stats.idle_share``)."""
from flamebench import stats


def read(rec):
    return stats.idle_share(rec)
