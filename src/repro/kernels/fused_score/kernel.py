"""Fused candidate-scoring attention — Pallas TPU kernel (FKE core).

One kernel computes the serving hot path that the framework previously
composed from four dispatches (host dequantize → ``kv[idx]`` row gather →
``concat(hist, cand)`` → masked attention):

  * **two-segment online softmax** — the query block streams over the
    pooled *history* KV blocks and then its own *candidate* (or suffix)
    KV block; the concatenation never materializes;
  * **in-kernel dequantization** — history K/V arrive in the pool's
    stored precision (int8 / bf16 / native) plus a per-(row, head) absmax
    scale; tiles are cast on the MXU input path and the scale is folded
    into the score / accumulator multiplies, so the dequantized history
    never touches HBM;
  * **index-folded dedup gather** — a scalar-prefetched per-q-block
    ``row_index [B, nq]`` drives the KV BlockSpec index map: q block
    ``qi`` of batch row ``b`` reads the blocks of pool row
    ``row_index[b, qi]`` directly, making the DSO's KV-row dedup free on
    every backend (no gathered copy, just redirected DMAs).  The per-q-
    block granularity is what DSO v2 segment packing rides on: one packed
    row carries candidate segments of several users, each q block steered
    to its own user's pooled history (segments aligned to ``bq`` on this
    path; ops.py samples the index at each block's first candidate).

Two masking modes share the machinery:

  ``cached``   SUMI candidate scoring: every query row sees the whole
               history plus exactly its own key (diagonal self block);
               ``steps = hist_steps + 1``.
  ``extend``   incremental history extension: suffix queries at absolute
               position ``prefix_len + i`` see the whole prefix plus the
               causal triangle of the suffix; ``steps = hist_steps + nq``
               with above-diagonal suffix blocks skipped via ``pl.when``.

A third serving workload — **generative decode** (FKE v2) — is cached
mode with a per-row ``lengths`` bound on the history segment: the pooled
operand is a PADDED, growing beam cache whose valid prefix per pool row
is ``lengths[row]``, so the history mask tightens from the static
``cols < s_hist`` to the prefetched ``cols < lens_ref[row]``.  Masked
positions contribute exact zeros to the online softmax (the ``where``
after ``exp`` is load-bearing for fully-masked blocks), so a padded
cache scores bitwise-identically to a tight one — cached/extend callers
pass ``lengths`` filled with ``s_hist``, making the bound a no-op.

The per-(row, head) scales and the per-row lengths ride in scalar
prefetch (SMEM) next to the ``row_index``; accumulators (m, l, acc) live
in VMEM scratch across the sequential innermost grid axis, exactly like
``kernels/flash_attention``.

The **pointwise variant** (:func:`hstu_score_kernel`, HSTU's attention)
keeps the ``row_index`` pool-row gather, the in-register dequant and the
packed-segment steering, and replaces the online-softmax update with
``acc += SiLU(s + bias) @ v`` and a final ``/ N``.  Its bias arrives
precomputed: one row per pool row on the cached path (every candidate of
a row sits at the same position and time), one row per query on the
extend path (``bias_kernel.py`` builds it there).  Extend mode keeps this
file's grid; cached mode (``pointwise_cached.py``) scores wide q blocks,
streaming a history once for every sub-block of a block that reads it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret
from repro.kernels.fused_score.pointwise_cached import hstu_cached_call

NEG_INF = -1e30


def _fused_kernel(idx_ref, lens_ref, ks_ref, vs_ref, q_ref, kh_ref, vh_ref,
                  kc_ref, vc_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  mode: str, h: int, g: int, bq: int, bk: int, sq: int,
                  s_hist: int, hist_steps: int, steps: int):
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    row = idx_ref[bh // h, qi]               # pool row of this q block
    kvh = (bh % h) // g                      # kv head of this q head

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _online_update(s, msk, v, v_scale):
        s = jnp.where(msk, s, NEG_INF)
        m_prev = m_ref[...]                                  # [bq, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(msk, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))
        if v_scale is not None:
            pv = pv * v_scale
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    @pl.when(kj < hist_steps)
    def _history_step():
        # pooled-history block: dequantize the tile in registers; the
        # per-(row, head) scale is constant over (S, D), so it folds into
        # the score and accumulator multiplies
        q = q_ref[0, 0].astype(jnp.float32)                  # [bq, D]
        k = kh_ref[0, 0].astype(jnp.float32)                 # [bk, D]
        v = vh_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        s = s * ks_ref[row, kvh]
        rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        # per-row valid-prefix bound (decode: growing padded beam caches);
        # cached/extend callers prefetch lens == s_hist, keeping this the
        # static history mask bitwise
        _online_update(s, (rows < sq) & (cols < lens_ref[row]), v,
                       vs_ref[row, kvh])

    @pl.when(_self_guard(mode, qi, kj, hist_steps))
    def _self_step():
        # fresh candidate / suffix block, full precision, no scale
        cj = qi if mode == "cached" else kj - hist_steps
        q = q_ref[0, 0].astype(jnp.float32)
        k = kc_ref[0, 0].astype(jnp.float32)                 # [bq, D]
        v = vc_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bq), 0)
        cols = cj * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bq), 1)
        ok = (rows < sq) & (cols < sq)
        if mode == "cached":
            msk = ok & (rows == cols)        # self key only (SUMI)
        else:
            msk = ok & (cols <= rows)        # causal within the suffix
        _online_update(s, msk, v, None)

    @pl.when(kj == steps - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _self_guard(mode: str, qi, kj, hist_steps: int):
    """Whether grid step ``kj`` of q block ``qi`` is a candidate/suffix
    step: the one diagonal block (cached), or a suffix block at or below
    the diagonal (extend: causal)."""
    if mode == "cached":
        return kj == hist_steps
    return (kj >= hist_steps) & (kj - hist_steps <= qi)


def _index_maps(mode: str, h: int, g: int, nq: int, hist_steps: int):
    """BlockSpec index maps both kernels share, over the grid (B*H, nq,
    steps) with the four scalar-prefetch refs trailing: the row index, then
    three the maps do not read (scales, and lengths or the self bias):
    query/output blocks, pooled-history blocks and candidate/suffix
    blocks."""

    def q_map(bh, qi, kj, idx_ref, pf1, pf2, pf3):
        return (bh // h, bh % h, qi, 0)

    def kh_map(bh, qi, kj, idx_ref, pf1, pf2, pf3):
        # the dedup/packing gather, folded into the block read: q block qi
        # of batch row b pulls the blocks of pool row idx_ref[b, qi]
        # (clamped for self steps, whose loaded block is unused)
        return (idx_ref[bh // h, qi], (bh % h) // g,
                jnp.minimum(kj, hist_steps - 1),
                0)  # flamecheck: kernel-ok(pure scalar clamp of a grid index; Python min fails on the traced kj)

    def kc_map(bh, qi, kj, idx_ref, pf1, pf2, pf3):
        return (bh // h, (bh % h) // g, _self_block(mode, qi, kj, nq,
                                                     hist_steps), 0)

    return q_map, kh_map, kc_map


def _self_block(mode: str, qi, kj, nq: int, hist_steps: int):
    """Candidate/suffix block read at grid step ``kj``: the q block's own
    (cached), or the suffix block ``kj - hist_steps`` clamped (extend)."""
    if mode == "cached":
        return qi
    return jnp.clip(kj - hist_steps, 0, nq - 1)


def fused_score_kernel(row_index, lengths, k_scale, v_scale, q, k_hist,
                       v_hist, k_cand, v_cand, *, mode: str, sq: int,
                       s_hist: int, bq: int = 128, bk: int = 128,
                       interpret: bool | None = None):
    """q [B,H,Mp,D] (pre-scaled); k_hist/v_hist [U,Hkv,Sp,D] stored dtype;
    lengths [U] int32 per-pool-row valid history prefix (<= s_hist; pass
    ``full(s_hist)`` for the static cached/extend masks);
    k_scale/v_scale [U,Hkv] f32 multipliers (1.0 for unquantized);
    k_cand/v_cand [B,Hkv,Mp,D]; row_index [B, Mp//bq] int32 per-q-block
    pool-row gather (constant per row for plain dedup; per-segment for
    DSO v2 packed rows).

    ``sq``/``s_hist`` are the unpadded query/history lengths; Mp/Sp/D are
    pre-padded to block and 128-lane multiples by ops.py (``s_hist >= 1``
    — an empty history segment is the caller's degenerate case).
    """
    if mode not in ("cached", "extend"):
        raise ValueError(mode)
    b, h, mp, d = q.shape
    hkv = k_hist.shape[1]
    g = h // hkv
    sp = k_hist.shape[2]
    nq = mp // bq
    hist_steps = sp // bk
    self_steps = 1 if mode == "cached" else nq
    steps = hist_steps + self_steps

    kernel = functools.partial(
        _fused_kernel, mode=mode, h=h, g=g, bq=bq, bk=bk, sq=sq,
        s_hist=s_hist, hist_steps=hist_steps, steps=steps)

    grid = (b * h, nq, steps)
    q_map, kh_map, kc_map = _index_maps(mode, h, g, nq, hist_steps)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,       # row_index, lengths, k_scale, v_scale
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), q_map),
            pl.BlockSpec((1, 1, bk, d), kh_map),
            pl.BlockSpec((1, 1, bk, d), kh_map),
            pl.BlockSpec((1, 1, bq, d), kc_map),
            pl.BlockSpec((1, 1, bq, d), kc_map),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # m (running max)
            pltpu.VMEM((bq, 1), jnp.float32),    # l (running denom)
            pltpu.VMEM((bq, d), jnp.float32),    # acc
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=default_interpret() if interpret is None else interpret,
    )(row_index, lengths, k_scale, v_scale, q, k_hist, v_hist, k_cand,
      v_cand)


# ---------------------------------------------------------------------------
# pointwise variant (HSTU): SiLU(s + bias) / N in place of the softmax
# ---------------------------------------------------------------------------

def _pointwise_extend_kernel(idx_ref, ks_ref, vs_ref, sb_ref, q_ref, kh_ref,
                             vh_ref, bh_ref, kc_ref, vc_ref, bc_ref, o_ref,
                             acc_ref, *, h: int, g: int, bq: int, bk: int,
                             sq: int, s_hist: int, hist_steps: int,
                             steps: int, n_norm: float):
    """Same grid, gather and dequant as :func:`_fused_kernel` in extend
    mode; each step adds ``SiLU(s + bias) @ v`` to the accumulator (no
    running max, no denominator) and the last step divides by ``n_norm``.
    ``bh_ref`` is the history bias block, one row per query; ``bc_ref`` the
    suffix bias block."""
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    row = idx_ref[bh // h, qi]               # pool row of this q block
    kvh = (bh % h) // g                      # kv head of this q head

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _accumulate(s, msk, v, v_scale):
        p = jnp.where(msk, jax.nn.silu(s), 0.0)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))
        if v_scale is not None:
            pv = pv * v_scale
        acc_ref[...] = acc_ref[...] + pv

    @pl.when(kj < hist_steps)
    def _history_step():
        q = q_ref[0, 0].astype(jnp.float32)                  # [bq, D]
        k = kh_ref[0, 0].astype(jnp.float32)                 # [bk, D]
        v = vh_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        s = s * ks_ref[row, kvh] + bh_ref[0]
        rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        _accumulate(s, (rows < sq) & (cols < s_hist), v, vs_ref[row, kvh])

    @pl.when(_self_guard("extend", qi, kj, hist_steps))
    def _suffix_step():
        cj = kj - hist_steps
        q = q_ref[0, 0].astype(jnp.float32)
        k = kc_ref[0, 0].astype(jnp.float32)                 # [bq, D]
        v = vc_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bq), 0)
        cols = cj * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bq), 1)
        s = s + bc_ref[0]
        msk = (rows < sq) & (cols < sq) & (cols <= rows)     # causal
        _accumulate(s, msk, v, None)

    @pl.when(kj == steps - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / n_norm).astype(o_ref.dtype)


def hstu_score_kernel(row_index, k_scale, v_scale, self_bias, q, k_hist,
                      v_hist, bias_hist, k_cand, v_cand, bias_cand=None, *,
                      mode: str, sq: int, s_hist: int, n_norm: float,
                      bq: int = 128, bk: int = 128, bs: int | None = None,
                      interpret: bool | None = None):
    """Pointwise attention over the same operands as
    :func:`fused_score_kernel` (q not pre-scaled): ``bias_hist``
    [U, R, Sp] f32 with R = 1 (cached: one row per pool row, broadcast
    over its candidates) or R = Mp (extend: one row per query);
    ``self_bias`` [1] f32, the cached self key's bias; ``bias_cand``
    [B, Mp, Mp] f32, the extend suffix's causal bias (None in cached
    mode).  Cached mode takes ``row_index`` [B, Mp // bs], a pool row per
    sub-block of ``bs`` rows (default ``bq``), and scores q blocks of
    ``bq`` rows (``pointwise_cached.py``); extend mode takes [B, Mp // bq]."""
    if mode not in ("cached", "extend"):
        raise ValueError(mode)
    b, h, mp, d = q.shape
    hkv = k_hist.shape[1]
    g = h // hkv
    sp = k_hist.shape[2]
    nq = mp // bq
    hist_steps = sp // bk
    interpret = default_interpret() if interpret is None else interpret
    if mode == "cached":
        return hstu_cached_call(
            row_index, k_scale, v_scale, self_bias, q, k_hist, v_hist,
            bias_hist, k_cand, v_cand, h=h, g=g, bq=bq, bs=bs or bq, bk=bk,
            hist_steps=hist_steps, n_norm=n_norm, interpret=interpret)
    steps = hist_steps + nq
    kernel = functools.partial(
        _pointwise_extend_kernel, h=h, g=g, bq=bq, bk=bk, sq=sq,
        s_hist=s_hist, hist_steps=hist_steps, steps=steps,
        n_norm=float(n_norm))
    q_map, kh_map, kc_map = _index_maps(mode, h, g, nq, hist_steps)

    def bh_map(bh, qi, kj, idx_ref, ks_ref, vs_ref, sb_ref):
        row, _, kblk, _ = kh_map(bh, qi, kj, idx_ref, ks_ref, vs_ref, sb_ref)
        return (row, qi, kblk)

    def bc_map(bh, qi, kj, idx_ref, ks_ref, vs_ref, sb_ref):
        return (bh // h, qi, _self_block(mode, qi, kj, nq, hist_steps))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,     # row_index, k_scale, v_scale, self_bias
        grid=(b * h, nq, steps),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), q_map),
            pl.BlockSpec((1, 1, bk, d), kh_map),
            pl.BlockSpec((1, 1, bk, d), kh_map),
            pl.BlockSpec((1, bq, bk), bh_map),
            pl.BlockSpec((1, 1, bq, d), kc_map),
            pl.BlockSpec((1, 1, bq, d), kc_map),
            pl.BlockSpec((1, bq, bq), bc_map),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), q_map),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],    # acc
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(row_index, k_scale, v_scale, self_bias, q, k_hist, v_hist, bias_hist,
      k_cand, v_cand, bias_cand)
