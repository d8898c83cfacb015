"""Median latency of the requests due in the window, from the time each
was due to its completion on the client; a failed or unfinished request
counts as missing (infinite)."""
from flamebench import stats


def read(rec):
    return stats.percentile(stats.latencies_ms(rec), 50)
