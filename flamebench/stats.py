"""Reductions the metric readers share: which requests a window counts,
latency percentiles, a kernel's roofline share and step MFU (the last two
through the record's model ``family``)."""
from __future__ import annotations

import math
from typing import List, Optional


def completed_in_window(rec: dict) -> List[dict]:
    """Requests that succeeded and completed inside the window."""
    t0, t1 = rec["window"]
    return [r for r in rec["requests"]
            if r["ok"] and t0 <= r["done"] <= t1]


def latencies_ms(rec: dict) -> List[float]:
    """Latency of every request due in the window, from its due time;
    infinite for a request that failed or never finished."""
    return [1e3 * (r["done"] - r["due"]) if r["ok"] else math.inf
            for r in rec["requests"]]


def percentile(values: List[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (no interpolation, so a missing request
    counts above every finished one); None when there are none or the
    percentile itself is missing."""
    if not values:
        return None
    v = sorted(values)
    x = v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]
    return None if math.isinf(x) else x


def idle_share(rec: dict) -> Optional[float]:
    """Share of the traced window in which no operation ran on the device,
    in percent."""
    t = rec.get("trace")
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def roofline_share(rec: dict, kernel: str) -> Optional[float]:
    """Kernel ``kernel``'s least time at the chip's peaks over its device
    time, in percent, over the traced window; None where the cell's family
    declares no such kernel or the window traced no call of it.

    The least time of a call is the larger of its FLOPs over the bf16 peak
    and its bytes over the HBM bandwidth, both from the family's
    ``Kernel.work`` on the call's extents (parsed from its HLO text), the
    model, the history window and the traced window's counters."""
    t = rec.get("trace")
    k = rec["family"].KERNELS.get(kernel)
    if t is None or k is None or rec.get("peaks") is None:
        return None
    calls = [c for c in t["kernels"] if c["kernel"] == kernel]
    if not calls:
        return None
    model, pk = rec["model"], rec["peaks"]
    c = rec["trace_counters"] or {}
    least = spent = 0.0
    for call in calls:
        flops, nbytes = k.work(call, model, rec["n_history"], c)
        least += max(flops / pk["bf16_flops_per_s"],
                     nbytes / pk["hbm_bytes_per_s"])
        spent += call["seconds"]
    return 100.0 * least / spent if spent > 0 else None


def step_mfu(rec: dict) -> Optional[float]:
    """Model FLOPs that the requests completed in the traced window needed
    (the family's ``request_flops``, from the traffic), over the device's
    busy time in that window times the bf16 peak, in percent."""
    t = rec.get("trace")
    if t is None or t["busy_s"] <= 0 or rec.get("peaks") is None:
        return None
    w0, w1 = rec["trace_window"]
    count = rec["family"].request_flops
    flops = 0.0
    for r in rec["requests"]:
        if r["ok"] and w0 <= r["done"] <= w1:
            q = r["req"]
            flops += count(rec["model"], rec["n_history"], r["m"],
                           new_user=q.new_user, grew=q.grew)
    if flops == 0:
        return None
    return 100.0 * flops / (t["busy_s"] * rec["peaks"]["bf16_flops_per_s"])
