"""The pointwise scoring kernel's (``hstu_score``) share of its roofline
over the traced window: the least time of its calls at the chip's peaks
over their summed device time (see ``stats.roofline_share``); nothing
where the cell's family runs no ``hstu_score``."""
from flamebench import stats


def read(rec):
    return stats.roofline_share(rec, "hstu_score")
