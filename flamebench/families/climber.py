"""The Climber family (arXiv:2502.09888; served by FLAME, arXiv:2509.22681).

What the benchmark knows of Climber, in the roles of ``families``: the
program's config, the weight layout, the plain reference's inputs and
answers (``climber_reference.py``), the FLOPs a request needs and the
``fused_score`` kernel's calls and least work.

A configuration's ``model`` holds the program's ``ModelConfig`` fields, with
``climber`` the ``ClimberConfig`` fields (blocks, layers per block, tasks,
head experts).

``flops_per_request`` and ``cached_flops_per_request`` are copies of the
analytic counts in ``core/sumi.py`` (kept here so that no change to the
program can change the yardstick).  The rest builds on them: the model
FLOPs a request needs (step MFU) and the work of one ``fused_score``
kernel call, counted from logical extents (head_dim 64, not the kernel's
128-lane padding).
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

from flamebench.families import Kernel
from flamebench.families import climber_reference as reference
from flamebench.trace import DTYPE_BYTES, shapes

N_SIDE_FEATURES = 12
POS_TABLE = 8192


def program_config(conf: dict):
    """The program's ModelConfig for a configuration file's ``model``."""
    import dataclasses

    from repro.configs import climber
    from repro.types import ClimberConfig

    m = dict(conf["model"])
    blocks = ClimberConfig(**m.pop("climber"))
    return dataclasses.replace(climber.CONFIG, climber=blocks, **m)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def layout(model: dict) -> dict:
    """Leaf path -> (shape, dtype name, init rule) for a Climber config:
    bfloat16 leaves, the per-layer temperatures in float32."""
    d, f, h = model["d_model"], model["d_ff"], model["n_heads"]
    hkv, hd = model["n_kv_heads"], model["head_dim"]
    c = model["climber"]
    nl, nb, e, t = c["layers_per_block"], c["num_blocks"], \
        c["num_experts_head"], c["num_tasks"]
    out = {
        "embed/embedding": ((model["vocab_size"], d), "bfloat16", 0.02),
        "pos_embed": ((POS_TABLE, d), "bfloat16", 0.02),
        "side_proj": ((N_SIDE_FEATURES, d), "bfloat16", "fan_in"),
        "gate_w": ((nb, d), "bfloat16", 0.02),
        "gate_b": ((nb, d), "bfloat16", "bias"),
        "out_norm/scale": ((d,), "bfloat16", "scale"),
        "out_norm/bias": ((d,), "bfloat16", "bias"),
        "experts_w1": ((e, d, d), "bfloat16", 1 / np.sqrt(d)),
        "experts_w2": ((e, d, d), "bfloat16", 1 / np.sqrt(d)),
        "task_gates": ((t, d, e), "bfloat16", 1 / np.sqrt(d)),
        "task_towers": ((t, d), "bfloat16", 1 / np.sqrt(d)),
    }
    for i in range(nb):
        b = f"blocks/b{i}"
        out.update({
            f"{b}/norm1/scale": ((nl, d), "bfloat16", "scale"),
            f"{b}/norm1/bias": ((nl, d), "bfloat16", "bias"),
            f"{b}/norm2/scale": ((nl, d), "bfloat16", "scale"),
            f"{b}/norm2/bias": ((nl, d), "bfloat16", "bias"),
            f"{b}/attn/wq": ((nl, d, h, hd), "bfloat16", 1 / np.sqrt(d)),
            f"{b}/attn/wk": ((nl, d, hkv, hd), "bfloat16", 1 / np.sqrt(d)),
            f"{b}/attn/wv": ((nl, d, hkv, hd), "bfloat16", 1 / np.sqrt(d)),
            f"{b}/attn/wo": ((nl, h, hd, d), "bfloat16",
                             1 / np.sqrt(h * hd)),
            f"{b}/ffn/w_up": ((nl, d, f), "bfloat16", 1 / np.sqrt(d)),
            f"{b}/ffn/w_down": ((nl, f, d), "bfloat16", 1 / np.sqrt(f)),
            f"{b}/temp": ((nl, 1), "float32", "temp"),
        })
    return out


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def reference_row(req, n_history: int) -> tuple:
    """(history window, side vector, candidates) of one request."""
    return (req.history[:n_history], reference.side_features(req.history),
            req.candidates)


def reference_scores(params, model: dict, rows: List[tuple],
                     max_slate: int, *, lowp: bool = False
                     ) -> List[np.ndarray]:
    """Task probabilities [m, T] of each row, all rows in one program with
    their slates padded to ``max_slate`` candidates and no generated
    tokens."""
    b = len(rows)
    hist = np.stack([r[0] for r in rows])
    side = np.stack([r[1] for r in rows])
    gen = np.zeros((b, 1), np.int32)
    glen = np.zeros(b, np.int32)
    cands = np.zeros((b, max_slate), np.int32)
    for j, r in enumerate(rows):
        cands[j, :len(r[2])] = r[2]
    p = reference.scores(params, model, hist, side, gen, glen, cands,
                         lowp=lowp)
    return [p[j, :len(r[2])] for j, r in enumerate(rows)]


# ---------------------------------------------------------------------------
# FLOPs
# ---------------------------------------------------------------------------

def flops_per_request(n_history: int, n_candidates: int, n_blocks: int,
                      layers_per_block: int, d_model: int, d_ff: int) -> float:
    """Analytic FLOPs of one SUMI forward (paper Table 2 reproduction)."""
    s_block = n_history // n_blocks + n_candidates
    per_tok_proj = 2 * (4 * d_model * d_model + 2 * d_model * d_ff)
    n_hist_b = n_history // n_blocks
    attn_pairs = n_hist_b * (n_hist_b + 1) / 2 + n_candidates * (n_hist_b + 1)
    per_layer = s_block * per_tok_proj + 2 * 2 * attn_pairs * d_model
    return n_blocks * layers_per_block * per_layer


def cached_flops_per_request(n_history: int, n_candidates: int, n_blocks: int,
                             layers_per_block: int, d_model: int,
                             d_ff: int) -> float:
    """Analytic FLOPs of a candidate-only pass against cached history K/V."""
    per_tok_proj = 2 * (4 * d_model * d_model + 2 * d_model * d_ff)
    n_hist_b = n_history // n_blocks
    attn_pairs = n_candidates * (n_hist_b + 1)
    per_layer = n_candidates * per_tok_proj + 2 * 2 * attn_pairs * d_model
    return n_blocks * layers_per_block * per_layer


def _dims(model: dict):
    c = model["climber"]
    return (c["num_blocks"], c["layers_per_block"], model["d_model"],
            model["d_ff"])


def _token_pass(model: dict, n_tokens: int, context: int) -> float:
    """FLOPs of ``n_tokens`` tokens per block attending to ``context``
    positions (themselves included), all layers of all blocks."""
    nb, nl, d, f = _dims(model)
    per_tok_proj = 2 * (4 * d * d + 2 * d * f)
    return nb * nl * n_tokens * (per_tok_proj + 2 * 2 * context * d)


def request_flops(model: dict, n_history: int, m: int, *, new_user: bool,
                  grew: bool) -> float:
    """Model FLOPs one request needs, counted from the traffic: the
    candidate pass at its real slate, the history encode when the run had
    not sent that user's history before, and the re-encoded suffix (one
    side token per block) when the history grew.  Recomputation after an
    eviction is not counted."""
    nb, nl, d, f = _dims(model)
    w = n_history // nb
    total = 0.0
    if new_user:
        total += flops_per_request(n_history, 0, nb, nl, d, f)
    elif grew:
        total += _token_pass(model, 1, w + 1)
    return total + cached_flops_per_request(n_history, m, nb, nl, d, f)


# ---------------------------------------------------------------------------
# the fused_score kernel
# ---------------------------------------------------------------------------

def kernel_call(op_text: str) -> Optional[dict]:
    """Shapes of one ``fused_score`` call from its HLO text (an op named
    ``%_fused_kernel_call``): result [B,H,Mp,Dp]; operands idx, lens, k/v
    scales, q, k/v history [U,Hkv,Sp,Dp], k/v candidates."""
    sh = shapes(op_text)
    if len(sh) < 7:
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
    """(FLOPs, bytes) of one ``fused_score`` call: ``rows`` x ``q_rows``
    query rows as dispatched, each over ``s_hist`` history positions plus
    its own (self) position; QK and PV; history K and V read once per
    distinct pool row at ``kv_bytes`` per element; queries, candidate K/V
    and outputs in bfloat16; per-(row, head) float32 scales."""
    q = rows * q_rows * heads * head_dim
    flops = 2 * 2 * q * (s_hist + 1)
    hist = unique_rows * s_hist * heads * head_dim * 2 * kv_bytes
    return float(flops), float(hist + 4 * q * 2 + unique_rows * heads * 8)


def fused_score_work(call: dict, model: dict, n_history: int,
                     counters: dict) -> tuple:
    """(FLOPs, bytes) one traced ``fused_score`` call needs at least.

    The call's rows, query rows, heads, padded history length and stored
    K/V dtype come from its HLO text (:func:`kernel_call`).  Logical extents
    then replace the padding: head_dim from the config (64, not 128 lanes)
    and history positions ``min(padded, window/blocks + 1)``.  Distinct
    pool rows read per call are the traced window's stacked KV rows of the
    kernel families (DSO rows dispatched less rows deduped, less the
    encode/extend/append rows) per ``cached`` and ``decode`` dispatch,
    held between 1 and what the call's shapes allow."""
    c = counters
    s0 = n_history // model["climber"]["num_blocks"] + 1
    other = sum(c.get(f"dso_chunks_{k}", 0.0)
                for k in ("encode", "extend", "append"))
    stacked = c.get("dso_rows_dispatched", 0.0) \
        - c.get("dso_dedup_rows_saved", 0.0) - other
    calls = c.get("dso_dispatches_cached", 0.0) \
        + c.get("dso_dispatches_decode", 0.0)
    per_call = stacked / calls if calls > 0 else 1.0
    cap = min(call["pool_rows"], call["rows"] * max(1, call["q_rows"] // 8))
    return kernel_work(
        rows=call["rows"], q_rows=call["q_rows"], heads=call["heads"],
        head_dim=model["head_dim"], s_hist=min(call["s_pad"], s0),
        unique_rows=min(max(per_call, 1.0), cap),
        kv_bytes=call["kv_bytes"])


KERNELS = {"fused_score": Kernel("%_fused_kernel_call", kernel_call,
                                 fused_score_work)}
