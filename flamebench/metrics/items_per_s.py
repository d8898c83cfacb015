"""Candidates scored in requests completed inside the window, over the
window's seconds."""
from flamebench import stats


def read(rec):
    done = stats.completed_in_window(rec)
    if not done:
        return None
    return sum(r["m"] for r in done) / rec["seconds"]
