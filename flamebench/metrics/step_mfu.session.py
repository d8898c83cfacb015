"""Model FLOPs of the requests completed in the traced window over the
device's busy time there times the chip's bf16 peak (see
``stats.step_mfu``)."""
from flamebench import stats


def read(rec):
    return stats.step_mfu(rec)
