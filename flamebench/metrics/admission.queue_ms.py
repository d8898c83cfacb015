"""Mean wait in the engine's admission queue (``ServeResponse.timings
["queue_s"]``) of the requests completed inside the window."""
from flamebench import stats


def read(rec):
    q = [r["queue_s"] for r in stats.completed_in_window(rec)
         if r["queue_s"] is not None]
    return 1e3 * sum(q) / len(q) if q else None
