"""Reductions the metric readers share: which requests a window counts,
latency percentiles, the kernel's roofline share and step MFU."""
from __future__ import annotations

import math
from typing import List, Optional

from flamebench import work


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


def roofline_share(rec: dict) -> Optional[float]:
    """``fused_score``'s least time at the chip's peaks over its device
    time, in percent, over the traced window.

    Calls and their shapes come from the kernel's HLO text in the trace
    (``trace.kernel_call``): rows and query rows as dispatched, heads, the
    padded history length and the stored K/V dtype.  Logical extents then
    replace the padding: head_dim from the config (64, not 128 lanes) and
    history positions ``min(padded, window/blocks + 1)``.  Distinct pool
    rows read per call are the traced window's stacked KV rows of the
    kernel families (DSO rows dispatched less rows deduped, less the
    encode/extend/append rows) per ``cached`` and ``decode`` dispatch,
    held between 1 and what the call's shapes allow."""
    t = rec.get("trace")
    if t is None or not t["kernels"] or rec.get("peaks") is None:
        return None
    model, pk = rec["model"], rec["peaks"]
    c = rec["trace_counters"] or {}
    s0 = rec["n_history"] // model["climber"]["num_blocks"] + 1
    other = sum(c.get(f"dso_chunks_{k}", 0.0)
                for k in ("encode", "extend", "append"))
    stacked = c.get("dso_rows_dispatched", 0.0) \
        - c.get("dso_dedup_rows_saved", 0.0) - other
    calls = c.get("dso_dispatches_cached", 0.0) \
        + c.get("dso_dispatches_decode", 0.0)
    per_call = stacked / calls if calls > 0 else 1.0
    least = spent = 0.0
    for k in t["kernels"]:
        cap = min(k["pool_rows"], k["rows"] * max(1, k["q_rows"] // 8))
        flops, nbytes = work.kernel_work(
            rows=k["rows"], q_rows=k["q_rows"], heads=k["heads"],
            head_dim=model["head_dim"], s_hist=min(k["s_pad"], s0),
            unique_rows=min(max(per_call, 1.0), cap),
            kv_bytes=k["kv_bytes"])
        least += max(flops / pk["bf16_flops_per_s"],
                     nbytes / pk["hbm_bytes_per_s"])
        spent += k["seconds"]
    return 100.0 * least / spent if spent > 0 else None


def step_mfu(rec: dict) -> Optional[float]:
    """Model FLOPs that the requests completed in the traced window needed
    (``work.request_flops``, from the traffic), over the device's busy
    time in that window times the bf16 peak, in percent."""
    t = rec.get("trace")
    if t is None or t["busy_s"] <= 0 or rec.get("peaks") is None:
        return None
    w0, w1 = rec["trace_window"]
    flops = 0.0
    for r in rec["requests"]:
        if r["ok"] and w0 <= r["done"] <= w1:
            q = r["req"]
            flops += work.request_flops(
                rec["model"], rec["n_history"], r["m"], new_user=q.new_user,
                grew=q.grew)
    if flops == 0:
        return None
    return 100.0 * flops / (t["busy_s"] * rec["peaks"]["bf16_flops_per_s"])
