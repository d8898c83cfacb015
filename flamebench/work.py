"""Operations and bytes the benchmark charges to the window's work.

``flops_per_request`` and ``cached_flops_per_request`` are copies of the
analytic counts in ``core/sumi.py`` (kept here so that no change to the
program can change the yardstick).  The rest builds on them: the model
FLOPs a request needs (step MFU) and the work of one ``fused_score``
kernel call, counted from logical extents (head_dim 64, not the kernel's
128-lane padding).
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


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


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a device that is not in the table is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]
