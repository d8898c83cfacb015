"""Reduce a profiler trace (``.xplane.pb``) to the numbers the benchmark
reads: the traced window, device busy time, each call of the kernels a
model family declares (``families.Kernel``) with its extents, the device ops
that took most time, and the longest idle gaps labelled by what the host
was doing in them.

Layout read here (JAX 0.9 on TPU v5e): one plane per chip named
``/device:TPU:<n>`` whose line ``XLA Ops`` holds every HLO op run on the
chip, named by its HLO text (``%<op> = bf16[...] custom-call(...)`` for a
kernel); host threads are lines of the plane ``/host:CPU``.  The benchmark
marks the traced window with a host ``TraceAnnotation`` named
:data:`WINDOW`, on the same clock.
"""
from __future__ import annotations

import re
from typing import Any, Dict, List, Mapping, Tuple

WINDOW = "flamebench.window"
# control-flow ops cover the ops they run: never a device op of their own
_CONTAINERS = ("%while", "%conditional", "%call")
_SHAPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")
#: bytes of one element of an HLO dtype
DTYPE_BYTES = {"s8": 1, "u8": 1, "bf16": 2, "f16": 2, "f32": 4, "s32": 4}


def shapes(op_text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """(dtype, dims) of every array in an HLO op's text: the result(s)
    first, then the operands in order."""
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _SHAPE.findall(op_text)]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _short(name: str) -> str:
    return name.split(" = ", 1)[0].strip()


def reduce(path: str, kernels: Mapping[str, Any], *, n_gaps: int = 10,
           n_ops: int = 10) -> dict:
    """Numbers of one trace file; times in seconds.  ``kernels`` maps a
    kernel's name to its ``families.Kernel``; each call of one is listed
    under ``kernels`` with its ``kernel`` name, its parsed extents and its
    ``seconds``."""
    from jax._src.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host_lines, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name == "/host:CPU":
            host_lines = list(plane.lines)
    win = None
    host_events = []
    for line in host_lines:
        for ev in line.events:
            t0, t1 = ev.start_ns, ev.start_ns + ev.duration_ns
            if ev.name == WINDOW:
                win = (t0, t1)
            else:
                host_events.append((t0, t1, ev.name))
    if win is None:
        raise ValueError(f"{path}: no host event named {WINDOW!r}")
    w0, w1 = win
    busy_total = 0.0
    calls: List[dict] = []
    op_time: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    for plane in devices:
        ops = [ln for ln in plane.lines if ln.name == "XLA Ops"]
        iv = []
        for line in ops:
            for ev in line.events:
                a = max(ev.start_ns, w0)
                b = min(ev.start_ns + ev.duration_ns, w1)
                if b <= a:
                    continue
                iv.append((a, b))
                name = ev.name
                if name.startswith(_CONTAINERS):
                    continue
                short = _short(name)
                op_time[short] = op_time.get(short, 0.0) + (b - a)
                for kname, k in kernels.items():
                    if name.startswith(k.op):
                        call = k.parse(name)
                        if call is not None:
                            call["kernel"] = kname
                            call["seconds"] = (b - a) * 1e-9
                            calls.append(call)
                        break
        merged = _union(iv)
        busy_total += sum(b - a for a, b in merged)
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges) - 1, 2)
                 if edges[i + 1] > edges[i]]
    if not devices:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    n_dev = len(devices)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n_gaps]
    overlap: List[Dict[str, float]] = [{} for _ in gaps]
    for t0, t1, name in host_events:
        for i, (a, b) in enumerate(gaps):
            ov = min(t1, b) - max(t0, a)
            if ov > 0:
                overlap[i][name] = overlap[i].get(name, 0.0) + ov
    idle = []
    for (a, b), ov in zip(gaps, overlap):
        top = sorted(ov.items(), key=lambda kv: -kv[1])[:2]
        label = " | ".join(n for n, _ in top) or "no host event"
        idle.append([label, (b - a) * 1e-9])
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:n_ops]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_total * 1e-9 / n_dev,
        "devices": len(devices),
        "kernels": calls,
        "device_ops": [[n, t * 1e-9 / n_dev] for n, t in top_ops],
        "idle_gaps": idle,
    }
