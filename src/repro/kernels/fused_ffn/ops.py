"""Public jit'd wrapper for the fused norm+FFN kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.fused_ffn.kernel import fused_ffn_kernel


@functools.partial(jax.jit, static_argnames=("activation", "bt", "bf",
                                             "interpret"))
def fused_ffn_2d(x, w_up, w_down, w_gate=None, norm_scale=None, *,
                 activation: str = "swiglu", bt: int = 256, bf: int = 512,
                 interpret: bool | None = None):
    """x [T,d] -> [T,d] fused norm+FFN."""
    t, d = x.shape
    f = w_up.shape[1]
    bt = min(bt, max(8, t))
    bf = min(bf, f)
    pad_t = (-t) % bt
    pad_f = (-f) % bf
    xp = jnp.pad(x, ((0, pad_t), (0, 0)))
    wu = jnp.pad(w_up, ((0, 0), (0, pad_f)))
    wd = jnp.pad(w_down, ((0, pad_f), (0, 0)))
    wg = jnp.pad(w_gate, ((0, 0), (0, pad_f))) if w_gate is not None else \
        jnp.zeros_like(wu)
    has_norm = norm_scale is not None
    scale = (norm_scale if has_norm else jnp.zeros((d,), x.dtype)).reshape(1, d)
    out = fused_ffn_kernel(xp, scale, wu, wg, wd, activation=activation,
                           has_norm=has_norm, bt=bt, bf=bf,
                           interpret=interpret)
    return out[:t]


def fused_ffn(x, params, *, activation: str = "swiglu", norm_scale=None,
              interpret: bool | None = None):
    """Model entry: x [...,d] with params {w_up, w_down[, w_gate]}."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    out = fused_ffn_2d(x2, params["w_up"], params["w_down"],
                       params.get("w_gate"), norm_scale,
                       activation=activation, interpret=interpret)
    return out.reshape(shape)
