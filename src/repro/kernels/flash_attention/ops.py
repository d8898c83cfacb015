"""Public jit'd wrapper for the mask-aware flash attention kernel.

Handles layout ([B,S,H,D] model layout -> [B,H,S,D] kernel layout), padding
of S to block multiples and D to the 128-lane width, softmax scaling, and the
interpret-mode fallback on CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention.kernel import flash_attention_kernel


def _pad_to(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("mode", "window", "n_history",
                                             "bq", "bk", "interpret",
                                             "q_offset"))
def flash_attention_bhsd(q, k, v, mode: str = "causal", *, window: int = 0,
                         n_history: int = 0, bq: int = 128, bk: int = 128,
                         interpret: bool | None = None, q_offset: int = 0):
    """q [B,H,Sq,D]; k,v [B,Hkv,Sk,D] -> [B,H,Sq,D]."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq = min(bq, max(8, 1 << (sq - 1).bit_length()))
    bk = min(bk, max(8, 1 << (sk - 1).bit_length()))
    bq = bk = min(bq, bk)  # kernel index math assumes square blocks
    scale = 1.0 / np.sqrt(d)
    qp = _pad_to(_pad_to(q * scale, 2, bq), 3, 128)
    kp = _pad_to(_pad_to(k, 2, bk), 3, 128)
    vp = _pad_to(_pad_to(v, 2, bk), 3, 128)
    out = flash_attention_kernel(qp.astype(q.dtype), kp, vp, mode=mode,
                                 window=window, n_history=n_history,
                                 sq=sq, sk=sk, bq=bq, bk=bk,
                                 interpret=interpret, q_offset=q_offset)
    return out[:, :, :sq, :d]


def flash_attention(q, k, v, mode: str = "causal", *, window: int = 0,
                    n_history: int = 0, interpret: bool | None = None,
                    q_offset: int = 0):
    """Model-layout entry point: q [B,Sq,H,D]; k,v [B,Sk,Hkv,D]."""
    o = flash_attention_bhsd(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                             jnp.swapaxes(v, 1, 2), mode, window=window,
                             n_history=n_history, interpret=interpret,
                             q_offset=q_offset)
    return jnp.swapaxes(o, 1, 2)
