"""Public jit'd wrapper for the flash-decode kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_decode.kernel import flash_decode_kernel


@functools.partial(jax.jit, static_argnames=("window", "bk", "interpret"))
def flash_decode(q, k_cache, v_cache, lengths, *, window: int = 0,
                 bk: int = 256, interpret: bool | None = None):
    """q [B,H,D] (one new token per sequence); caches [B,S,Hkv,D];
    lengths [B].  Returns [B,H,D]."""
    b, h, d = q.shape
    s = k_cache.shape[1]
    hkv = k_cache.shape[2]
    g = h // hkv
    bk = min(bk, max(8, 1 << (s - 1).bit_length()))
    pad_s = (-s) % bk
    pad_d = (-d) % 128
    if pad_s or pad_d:
        widths = ((0, 0), (0, pad_s), (0, 0), (0, pad_d))
        k_cache = jnp.pad(k_cache, widths)
        v_cache = jnp.pad(v_cache, widths)
    # fold the softmax scale here: the kernel must not divide by the PADDED d
    qq = (q * (1.0 / (d ** 0.5))).astype(q.dtype).reshape(b, hkv, g, d)
    if pad_d:
        qq = jnp.pad(qq, ((0, 0), (0, 0), (0, 0), (0, pad_d)))
    out = flash_decode_kernel(qq, k_cache, v_cache,
                              lengths.reshape(b, 1).astype(jnp.int32),
                              window=window, bk=bk, interpret=interpret)
    return out[..., :d].reshape(b, h, d)
