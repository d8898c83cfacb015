"""The HSTU family (Zhai et al., ICML 2024, arXiv:2402.17152), served by
FLAME's engine.

What the benchmark knows of HSTU, in the roles of ``families``: the
program's config, the weight layout, the plain reference's inputs and
answers (``hstu_reference.py``), the FLOPs a request needs and the calls
and least work of the pointwise scoring kernel (``hstu_score``).

A configuration's ``model`` holds the program's ``ModelConfig`` fields,
with ``hstu`` the ``HSTUConfig`` fields (sequence length N, time buckets,
tasks).  FLOPs are counted here, from the traffic and the widths served
(head_dim 64 logical, not the kernel's 128-lane padding), so that no
change to the program can change the yardstick.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

from flamebench.families import Kernel
from flamebench.families import hstu_reference as reference
from flamebench.trace import DTYPE_BYTES, shapes


def program_config(conf: dict):
    """The program's ModelConfig for a configuration file's ``model``; a
    program without the HSTU family is an error naming it."""
    try:
        from repro.types import HSTUConfig, ModelConfig
    except ImportError as e:
        raise ValueError(f"model family 'hstu': the program has no "
                         f"HSTUConfig to build it from ({e})") from None

    m = dict(conf["model"])
    return ModelConfig(name="hstu", family="hstu",
                       hstu=HSTUConfig(**m.pop("hstu")), **m)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def layout(model: dict) -> dict:
    """Leaf path -> (shape, dtype name, init rule), all bfloat16.  The
    relative bias tables are drawn at unit scale, so that position and time
    move the scores as much as the query-key products do."""
    d, h, hd, nl = (model["d_model"], model["n_heads"], model["head_dim"],
                    model["n_layers"])
    c = model["hstu"]
    w = 4 * h * hd
    return {
        "embed/embedding": ((model["vocab_size"], d), "bfloat16", 0.02),
        "pos_embed": ((c["max_seq_len"] + 1, d), "bfloat16", 0.02),
        "layers/uvqk": ((nl, d, w), "bfloat16", 1 / np.sqrt(d)),
        "layers/uvqk_b": ((nl, w), "bfloat16", "bias"),
        "layers/out": ((nl, h * hd, d), "bfloat16", 1 / np.sqrt(h * hd)),
        "layers/out_b": ((nl, d), "bfloat16", "bias"),
        "layers/pos_bias": ((nl, c["max_seq_len"] + 1), "bfloat16", 1.0),
        "layers/time_bias": ((nl, c["num_time_buckets"] + 1), "bfloat16",
                             1.0),
        "out_norm/scale": ((d,), "bfloat16", "scale"),
        "out_norm/bias": ((d,), "bfloat16", "bias"),
        "head_w": ((d, c["num_tasks"]), "bfloat16", 1 / np.sqrt(d)),
        "head_b": ((c["num_tasks"],), "bfloat16", "bias"),
    }


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def reference_row(req, n_history: int) -> tuple:
    """(history window, its action times, candidates) of one request."""
    window = req.history[:n_history]
    return (window, reference.action_times(req.user_id, window),
            req.candidates)


def reference_scores(params, model: dict, rows: List[tuple],
                     max_slate: int, *, lowp: bool = False
                     ) -> List[np.ndarray]:
    """Task probabilities [m, T] of each row, one row at a time with its
    slate padded to ``max_slate`` candidates (candidates never see each
    other, so the padding changes no answer)."""
    out = []
    for ids, times, cands in rows:
        padded = np.zeros(max_slate, np.int32)
        padded[:len(cands)] = cands
        p = reference.scores(params, model, ids, times, padded, lowp=lowp)
        out.append(p[:len(cands)])
    return out


# ---------------------------------------------------------------------------
# FLOPs
# ---------------------------------------------------------------------------

def _dims(model: dict):
    return (model["n_layers"], model["d_model"],
            model["n_heads"] * model["head_dim"])


def _proj_flops(model: dict) -> float:
    """Projection FLOPs of one token in one layer: f1 (d -> 4 h D) and f2
    (h D -> d)."""
    _, d, hd = _dims(model)
    return 2 * (d * 4 * hd + hd * d)


def encode_flops(model: dict, n: int) -> float:
    """FLOPs of a causal encode of ``n`` actions: projections of every
    token, and QK and AV over the n (n + 1) / 2 causal pairs; no FFN."""
    nl, _, hd = _dims(model)
    return nl * (n * _proj_flops(model) + 2 * 2 * hd * n * (n + 1) / 2)


def cached_flops(model: dict, n: int, m: int) -> float:
    """FLOPs of ``m`` candidates against ``n`` pooled history positions:
    their projections, and QK and AV over the history and themselves."""
    nl, _, hd = _dims(model)
    return nl * m * (_proj_flops(model) + 2 * 2 * hd * (n + 1))


def request_flops(model: dict, n_history: int, m: int, *, new_user: bool,
                  grew: bool) -> float:
    """Model FLOPs one request needs, counted from the traffic: the
    candidate pass at its real slate, and the history encode when the run
    had not sent that user's history before.  A history that grew past the
    window re-encodes nothing (the window and its action times are
    unchanged; no side token), so growth adds no FLOPs.  Recomputation
    after an eviction is not counted."""
    total = encode_flops(model, n_history) if new_user else 0.0
    return total + cached_flops(model, n_history, m)


# ---------------------------------------------------------------------------
# the pointwise scoring kernel
# ---------------------------------------------------------------------------

def kernel_call(op_text: str) -> Optional[dict]:
    """Shapes of one ``hstu_score`` call from its HLO text (an op named
    ``%_hstu_kernel_call``): result [B,H,Mp,Dp]; operands idx, k/v scales,
    self bias, q, k/v history [U,Hkv,Sp,Dp], history bias [U,R,Sp], k/v
    candidates (and the extend suffix's bias)."""
    sh = shapes(op_text)
    if len(sh) < 11:
        return None
    out = sh[0][1]
    kv_dtype, kh = sh[6]
    if len(out) != 4 or len(kh) != 4:
        return None
    return {"rows": out[0], "heads": out[1], "q_rows": out[2],
            "pool_rows": kh[0], "s_pad": kh[2],
            "kv_bytes": DTYPE_BYTES.get(kv_dtype, 2)}


def kernel_work(*, rows: int, q_rows: int, heads: int, head_dim: int,
                s_hist: int, unique_rows: float, kv_bytes: int) -> tuple:
    """(FLOPs, bytes) of one ``hstu_score`` call: ``rows`` x ``q_rows``
    query rows as dispatched, each over ``s_hist`` history positions plus
    its own; QK and AV.  Read once per distinct pool row: history K and V
    at ``kv_bytes`` per element, the time-derived bias at 4 bytes a
    position (an int32 time's size) and per-(row, head) float32 scales;
    queries, candidate K/V and outputs in bfloat16."""
    q = rows * q_rows * heads * head_dim
    flops = 2 * 2 * q * (s_hist + 1)
    hist = unique_rows * s_hist * (heads * head_dim * 2 * kv_bytes + 4)
    return float(flops), float(hist + 4 * q * 2 + unique_rows * heads * 8)


def hstu_score_work(call: dict, model: dict, n_history: int,
                    counters: dict) -> tuple:
    """(FLOPs, bytes) one traced ``hstu_score`` call needs at least: its
    rows, query rows, heads, padded history and stored dtype from its HLO
    text; head_dim from the config and history positions
    ``min(padded, window)``; distinct pool rows per call the traced
    window's stacked KV rows of ``cached`` dispatches (rows dispatched
    less rows deduped, less encode/extend rows) per ``cached`` dispatch,
    held between 1 and what the call's shapes allow."""
    c = counters
    other = sum(c.get(f"dso_chunks_{k}", 0.0) for k in ("encode", "extend"))
    stacked = c.get("dso_rows_dispatched", 0.0) \
        - c.get("dso_dedup_rows_saved", 0.0) - other
    calls = c.get("dso_dispatches_cached", 0.0)
    per_call = stacked / calls if calls > 0 else 1.0
    cap = min(call["pool_rows"], call["rows"] * max(1, call["q_rows"] // 8))
    return kernel_work(
        rows=call["rows"], q_rows=call["q_rows"], heads=call["heads"],
        head_dim=model["head_dim"], s_hist=min(call["s_pad"], n_history),
        unique_rows=min(max(per_call, 1.0), cap),
        kv_bytes=call["kv_bytes"])


KERNELS = {"hstu_score": Kernel("%_hstu_kernel_call", kernel_call,
                                hstu_score_work)}
