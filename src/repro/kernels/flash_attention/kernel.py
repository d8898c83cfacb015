"""Mask-aware flash attention — Pallas TPU kernel (the FKE attention plug-in).

Online-softmax flash attention with GQA and four mask modes.  The mask
structure is *static*, so whole KV blocks outside the mask are never visited:

  full     grid kv steps = nk (all blocks)
  causal   grid kv steps = nk, blocks with kj > qi skipped via pl.when
           (no FLOPs; the DMA for a skipped block is hidden by the pipeline)
  sliding  grid kv steps = ceil((window+bq)/bk)+1 — the index_map slides the
           KV window with the q block: compute AND bandwidth scale with
           S*window instead of S^2 (true block skipping)
  sumi     grid kv steps = ceil(n_history/bk)+1 — candidates only ever see
           history blocks plus their own diagonal block, the TPU analogue of
           the paper's HSTU-style mask-aware kernel: per-candidate compute is
           O(n_history + bq), independent of the number of candidates

Accumulators (m, l, acc) live in VMEM scratch and persist across the
sequential innermost grid axis; the MXU sees [bq, D] x [D, bk] matmuls with
D padded to a multiple of 128 (lane width) by ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret

NEG_INF = -1e30


def _kv_steps(mode: str, nk: int, bq: int, bk: int, window: int,
              n_history: int, q_offset: int = 0) -> int:
    if mode == "sliding":
        return min(nk, (window + bq + bk - 1) // bk + 1)
    if mode == "sumi":
        nhb = min(nk, (n_history + bk - 1) // bk)
        # q_offset > 0 (cached-history path): every query is a candidate, so
        # all history blocks are visited plus the block(s) holding its own
        # key — the offset need not be bk-aligned, so the bq-wide self range
        # can straddle two KV blocks
        return nhb + (2 if q_offset else 1)
    return nk


def _k_index(mode: str, qi, kj, *, nk: int, bq: int, bk: int, window: int,
             n_history: int, steps: int, q_offset: int = 0):
    """Map (q block, kv step) -> kv block index (may be clamped; guard masks
    duplicates)."""
    diag = (q_offset + qi * bq + bq - 1) // bk  # block holding the diagonal
    if mode == "sliding":
        raw = diag + kj - (steps - 1)          # last step = diagonal block
        return jnp.clip(raw, 0, nk - 1)
    if mode == "sumi":
        if q_offset:
            nhb = steps - 2
            d0 = (q_offset + qi * bq) // bk    # first block of the self range
            return jnp.where(kj < nhb, jnp.minimum(kj, nk - 1),
                             jnp.clip(jnp.where(kj == nhb, d0, diag),
                                      0, nk - 1))
        nhb = steps - 1
        return jnp.where(kj < nhb, jnp.minimum(kj, nk - 1),
                         jnp.minimum(diag, nk - 1))
    return kj


def _guard(mode: str, qi, kj, *, nk: int, bq: int, bk: int, window: int,
           n_history: int, steps: int, q_offset: int = 0):
    """True when this (q block, kv step) must be computed (fresh + visible)."""
    if mode == "full":
        return jnp.bool_(True)
    diag = (q_offset + qi * bq + bq - 1) // bk
    if mode == "causal":
        return kj <= diag
    if mode == "sliding":
        raw = diag + kj - (steps - 1)
        return (raw >= 0) & (raw <= diag)
    if mode == "sumi":
        if q_offset:
            # cached-history path: all queries are candidates.  History
            # blocks [0, nhb) are always visited; the two trailing steps
            # cover the (possibly straddling) self range, skipping blocks
            # the history sweep already produced and pure-padding blocks.
            nhb = steps - 2
            d0 = (q_offset + qi * bq) // bk
            d1 = diag
            self0 = (kj == nhb) & (d0 >= nhb) & (d0 < nk)
            self1 = (kj == nhb + 1) & (d1 >= nhb) & (d1 < nk) & (d1 > d0)
            return (kj < nhb) | self0 | self1
        nhb = steps - 1
        hist_step = (kj < nhb) & (kj <= diag)
        # diagonal step only needed when this q block extends past the
        # history blocks already visited
        diag_step = (kj == nhb) & (diag >= nhb)
        return hist_step | diag_step
    raise ValueError(mode)


def _element_mask(mode: str, rows, cols, *, window: int, n_history: int,
                  sq: int, sk: int, q_offset: int = 0):
    ok = (rows < sq) & (cols < sk)          # trim padding (rows are local)
    if mode == "full":
        return ok
    if mode == "causal":
        # q_offset > 0 (incremental history extension): suffix query row i
        # sits at absolute KV position q_offset + i
        return ok & (cols <= rows + q_offset)
    if mode == "sliding":
        return ok & (cols <= rows) & (rows - cols < window)
    if mode == "sumi":
        abs_rows = rows + q_offset          # absolute position in the KV axis
        hist = cols <= abs_rows
        cand = (cols < n_history) | (cols == abs_rows)
        return ok & jnp.where(abs_rows < n_history, hist, cand)
    raise ValueError(mode)


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               mode: str, bq: int, bk: int, window: int, n_history: int,
               sq: int, sk: int, nk: int, steps: int, scale: float,
               q_offset: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    guard = _guard(mode, qi, kj, nk=nk, bq=bq, bk=bk, window=window,
                   n_history=n_history, steps=steps, q_offset=q_offset)

    @pl.when(guard)
    def _compute():
        kidx = _k_index(mode, qi, kj, nk=nk, bq=bq, bk=bk, window=window,
                        n_history=n_history, steps=steps, q_offset=q_offset)
        q = q_ref[0, 0].astype(jnp.float32) * scale          # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)                  # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [bq, bk]
        rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = kidx * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        msk = _element_mask(mode, rows, cols, window=window,
                            n_history=n_history, sq=sq, sk=sk,
                            q_offset=q_offset)
        s = jnp.where(msk, s, NEG_INF)
        m_prev = m_ref[...]                                  # [bq, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(msk, p, 0.0)
        corr = jnp.exp(m_prev - m_new)                       # [bq, 1]
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new

    @pl.when(kj == steps - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, mode: str, window: int = 0,
                           n_history: int = 0, sq: int, sk: int,
                           bq: int = 128, bk: int = 128,
                           interpret: bool | None = None, q_offset: int = 0):
    """q [B,H,Sqp,D], k/v [B,Hkv,Skp,D] (pre-padded to block/lane multiples).

    ``sq``/``sk`` are the *unpadded* lengths (padding is masked out).
    ``q_offset`` shifts query positions against KV positions (sumi only):
    the cached-history path runs M candidate queries against n_history
    cached K/V rows followed by the candidates' own K/V, so query row i
    sits at absolute position ``q_offset + i``.
    Softmax scale must be folded by the caller via ``scale``-preserving
    convention: this kernel applies 1/sqrt(D_real) via the ``scale`` closure
    in ops.py — here q is scaled already, so scale=1.
    """
    if q_offset and mode not in ("sumi", "causal"):
        # block selection honors the offset for every mode, but the
        # sliding element mask still uses local row positions — fail
        # loudly rather than return silently-masked zeros
        raise NotImplementedError(
            f"q_offset is only supported for mode in ('sumi', 'causal'), "
            f"got {mode!r}")
    if q_offset and bq > bk:
        # the offset self range of a q block spans <= 2 KV blocks only for
        # bq <= bk (ops.py always passes square blocks); wider q blocks
        # would silently drop candidates' own keys
        raise NotImplementedError(
            f"q_offset needs bq <= bk, got bq={bq} bk={bk}")
    b, h, sqp, d = q.shape
    hkv = k.shape[1]
    g = h // hkv
    skp = k.shape[2]
    nq = sqp // bq
    nk = skp // bk
    steps = _kv_steps(mode, nk, bq, bk, window, n_history, q_offset)

    kernel = functools.partial(
        _fa_kernel, mode=mode, bq=bq, bk=bk, window=window,
        n_history=n_history, sq=sq, sk=sk, nk=nk, steps=steps, scale=1.0,
        q_offset=q_offset)

    grid = (b * h, nq, steps)

    def q_map(bh, qi, kj):
        return (bh // h, bh % h, qi, 0)

    def kv_map(bh, qi, kj):
        kidx = _k_index(mode, qi, kj, nk=nk, bq=bq, bk=bk, window=window,
                        n_history=n_history, steps=steps, q_offset=q_offset)
        return (bh // h, (bh % h) // g, kidx, 0)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), q_map),
            pl.BlockSpec((1, 1, bk, d), kv_map),
            pl.BlockSpec((1, 1, bk, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), q_map),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # m (running max)
            pltpu.VMEM((bq, 1), jnp.float32),    # l (running denom)
            pltpu.VMEM((bq, d), jnp.float32),    # acc
        ],
        interpret=default_interpret() if interpret is None else interpret,
    )(q, k, v)
