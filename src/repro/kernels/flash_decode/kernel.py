"""Flash-decode — Pallas TPU kernel for single-token attention over a long
KV cache (the serving-side FKE hot spot).

Decode attention is memory-bound: the whole job is streaming the valid
cache prefix HBM->VMEM once.  The kernel tiles the cache sequence axis;
blocks entirely past ``length`` (or before ``length-window``) are skipped
via pl.when, so HBM traffic scales with the *valid* prefix, not the cache
allocation.  All G q-heads of one KV head are processed together, giving
the MXU a [G, D] x [D, bk] matmul per block.

Grid = (B * Hkv, S/bk) with online-softmax scratch carried across the
sequential cache axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret

NEG_INF = -1e30


def _fd_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               bk: int, nk: int, window: int, scale: float):
    kj = pl.program_id(1)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[0, 0]
    lo = (length - window) if window else 0
    # block [kj*bk, kj*bk+bk) intersects the valid range [lo, length)?
    guard = (kj * bk < length) & (kj * bk + bk > lo)

    @pl.when(guard)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale           # [G, D]
        k = k_ref[0, :, 0].astype(jnp.float32)                # [bk, D]
        v = v_ref[0, :, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [G, bk]
        pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        ok = pos < length
        if window:
            ok = ok & (pos >= length - window)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new

    @pl.when(kj == nk - 1)
    def _final():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)) \
            .astype(o_ref.dtype)


def flash_decode_kernel(q, k_cache, v_cache, lengths, *, window: int = 0,
                        bk: int = 256, interpret: bool | None = None):
    """q [B,Hkv,G,D]; caches [B,S,Hkv,D]; lengths [B,1] i32.

    Returns [B,Hkv,G,D].  S must be a multiple of bk (ops.py pads)."""
    b, hkv, g, d = q.shape
    s = k_cache.shape[1]
    nk = s // bk
    # softmax scale is folded into q by ops.py (d here may be lane-padded)
    kernel = functools.partial(_fd_kernel, bk=bk, nk=nk, window=window,
                               scale=1.0)

    def q_map(bh, kj):
        return (bh // hkv, bh % hkv, 0, 0)

    def kv_map(bh, kj):
        return (bh // hkv, kj, bh % hkv, 0)

    return pl.pallas_call(
        kernel,
        grid=(b * hkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1), lambda bh, kj: (bh // hkv, 0)),   # lengths
            pl.BlockSpec((1, 1, g, d), q_map),
            pl.BlockSpec((1, bk, 1, d), kv_map),
            pl.BlockSpec((1, bk, 1, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), q_map),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        interpret=default_interpret() if interpret is None else interpret,
    )(lengths, q, k_cache, v_cache)
