"""Public jit'd wrapper for the RWKV-6 chunked wkv scan kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.rwkv6_scan.kernel import rwkv6_scan_kernel


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(r, k, v, w_log, u, state=None, *, chunk: int = 64,
               interpret: bool | None = None):
    """r,k,v,w_log [B,S,H,D]; u [H,D]; state [B,H,D,D] (optional).

    Returns (o [B,S,H,D], final_state [B,H,D,D])."""
    b, s, h, d = r.shape
    chunk = min(chunk, s)
    pad = (-s) % chunk
    sp = s + pad

    def to_bh(x):
        x = jnp.moveaxis(x, 2, 1).reshape(b * h, s, d)
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0)))

    rb, kb, vb = to_bh(r), to_bh(k), to_bh(v)
    # pad decay with log(1)=0 so padded steps don't decay the state
    wb = to_bh(w_log)
    ub = jnp.broadcast_to(u[None], (b, h, d)).reshape(b * h, 1, d)
    s0 = (state.reshape(b * h, d, d).astype(jnp.float32) if state is not None
          else jnp.zeros((b * h, d, d), jnp.float32))
    o, sf = rwkv6_scan_kernel(rb, kb, vb, wb, ub, s0, chunk=chunk,
                              interpret=interpret)
    o = jnp.moveaxis(o[:, :s].reshape(b, h, s, d), 1, 2)
    return o, sf.reshape(b, h, d, d)
