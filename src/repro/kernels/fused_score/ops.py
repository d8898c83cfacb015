"""Public wrappers for the fused candidate-scoring engine (FKE).

Entry points (model layout, [B,S,H,D]):

  ``fused_cached_attention``  candidate-only SUMI scoring against pooled
                              history K/V (quantized operands + dedup
                              ``row_index`` welcome)
  ``fused_extend_attention``  causal suffix extension against pooled
                              prefix K/V
  ``fused_decode_attention``  generative-decode candidate scoring: cached
                              mode against PADDED growing beam caches,
                              bounded per pool row by ``lengths`` (FKE v2)
  ``hstu_cached_attention``   the pointwise variant (HSTU): SiLU(s + bias)
  ``hstu_extend_attention``   / N over the same operands, no softmax
  ``hstu_relative_bias``      HSTU's position + time-bucket bias of a
                              query block over its keys
  ``block_epilogue``          out-projection + residual + norm + FFN for
                              one transformer-block layer step, reusing
                              ``kernels/fused_ffn`` on TPU

Each attention op has two execution paths behind one signature:

  ``path="kernel"``  the Pallas kernel (``kernel.py``): real TPU target,
                     interpret-mode on CPU for the parity suite;
  ``path="jnp"``     an XLA-fused two-segment formulation of the *same*
                     restructured computation — no ``concat(hist, cand)``
                     materialization, no dense SUMI mask (the history
                     segment is fully visible and the self segment is the
                     diagonal, so masking disappears algebraically), the
                     dequant scale folded into the score/accumulator
                     multiplies, and the dedup gather applied to the
                     *stored* (int8/bf16) values rather than dequantized
                     f32 rows.  This is what makes ``impl="fused"`` a real
                     speedup on the CPU backend, where interpret-mode
                     Pallas would be pure overhead;
  ``path="auto"``    kernel on TPU, jnp elsewhere.

Both paths are gated against ``ref.py`` (the dequantize → gather → concat
→ reference-attention oracle) in ``tests/test_fke.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import default_interpret
from repro.kernels.fused_score.bias_kernel import (LANES,
                                                   TIME_BUCKET_WIDTH,
                                                   time_bias_kernel)
from repro.kernels.fused_score.kernel import (fused_score_kernel,
                                              hstu_score_kernel)
from repro.kernels.fused_score.ref import dequantize_values


def _auto_path() -> str:
    """The Pallas kernel where it compiles for real; the jnp formulation
    where it would only be interpreted."""
    return "jnp" if default_interpret() else "kernel"


# Packed-dispatch alignment contract: every segment of a 2-D (segment-
# packed) ``row_index`` that reaches the kernel path starts at a multiple
# of this many candidates — the f32 sublane tile of the kernel's q
# (sub-)blocks.  The SegmentPacker's ``align`` (core/dso.py) produces it;
# with q blocks of this size, sampling the index at each block's first
# candidate never reads across a segment boundary.
PACK_ALIGN = 8


# Segment-packed (2-D row_index) histories at/above this length skip the
# per-candidate [B,M,S,Hkv,D] value gather and score via a dense all-rows
# GEMM + exact one-hot selection instead.  The gather turns the score
# contraction into M independent [1,D]x[D,S] GEMVs per batch row (poor
# arithmetic intensity) and materializes an M-times-replicated history
# operand; the dense form keeps the stored [U,S,Hkv,D] pool operand in
# place and contracts it with ALL candidates in one [B*M*G, D]x[D, U*S]
# GEMM, then selects each candidate's own row with a 0/1 one-hot einsum.
# The selection itself is exact (multiply by 1.0 / add 0.0 are lossless
# in IEEE-754); only the usual contraction-order reassociation separates
# the two forms.  The U-fold extra FLOPs only pay off once S is long
# enough for GEMM efficiency to dominate — short histories keep the
# gather.
_SEG_GEMM_MIN_S = 128


def _norm_scale(scale, u: int, hkv: int):
    """Pool scales arrive [U,1,Hkv,1] (per-layer slice of the per-(layer,
    head) absmax); normalize to [U,Hkv] with the int8 /127 folded in."""
    if scale is None:
        return None
    return (jnp.asarray(scale, jnp.float32) / 127.0).reshape(u, hkv)


# ---------------------------------------------------------------------------
# fused jnp fast path
# ---------------------------------------------------------------------------

def _segment_scores(qf, k_seg, scale):
    """qf [B,M,Hkv,g,D] (f32, pre-scaled) x k_seg [B,S,Hkv,D] (stored
    dtype) -> scores [B,Hkv,g,M,S] with the dequant scale folded in."""
    s = jnp.einsum("bmhgd,bshd->bhgms", qf, k_seg.astype(jnp.float32))
    if scale is not None:
        s = s * scale[:, :, None, None, None]        # [B,Hkv] broadcast
    return s


@functools.partial(jax.jit, static_argnames=("mode",))
def _fused_jnp(q, k_hist, v_hist, k_cand, v_cand, k_scale, v_scale,
               row_index, lengths, mode: str):
    """Two-segment online-merged attention, no concat / no dense mask.

    ``cached``: history segment fully visible, self segment = one key per
    query (an O(M·D) einsum instead of the O(M²·D) masked block).
    ``extend``: prefix segment fully visible, suffix segment causal.

    ``lengths`` (decode): per-pool-row valid history prefix over a padded
    stored operand.  Masked columns are forced to exact -inf before the
    segment max and exact 0 after the exp — both rewrites are bitwise
    no-ops for fully-valid rows (``where(True, x, ·) == x``), which is
    what keeps fused decode at zero generated tokens bitwise equal to
    fused candidate scoring, and the post-exp zero is what keeps a fully
    masked row (lengths == 0) exact rather than NaN.
    """
    b, m, h, d = q.shape
    hkv = k_cand.shape[2]
    g = h // hkv
    qf = q.astype(jnp.float32).reshape(b, m, hkv, g, d) / np.sqrt(d)
    seg = row_index is not None and row_index.ndim == 2
    seg_gemm = seg and k_hist.shape[1] >= _SEG_GEMM_MIN_S
    onehot = None
    hist_ok = None
    if lengths is not None:
        lens = jnp.asarray(lengths, jnp.int32)
        if row_index is not None:
            lens = jnp.take(lens, row_index, axis=0)     # [B] or [B,M]
        pos = jnp.arange(k_hist.shape[1])
        if lens.ndim == 2:
            hist_ok = (pos[None, None, :] <
                       lens[:, :, None])[:, None, None]  # [b,1,1,m,S]
        else:
            hist_ok = (pos[None, :] <
                       lens[:, None])[:, None, None, None]   # [b,1,1,1,S]
    if row_index is not None:
        # the dedup gather runs on the STORED values (int8: 4x fewer
        # bytes than the dequantized rows the framework path gathered).
        # A 2-D (per-candidate) index — DSO v2 segment packing — gathers
        # each candidate's own pool row: [B,M,S,Hkv,D] history operands
        # and [B,M,Hkv] scales.  At long histories (seg_gemm) the VALUE
        # gathers are skipped — scoring contracts the stored pool rows
        # directly (see _SEG_GEMM_MIN_S) — but the tiny scale gathers
        # stay, so the dequant multiply is per-candidate in both forms.
        if not seg_gemm:
            k_hist = jnp.take(k_hist, row_index, axis=0)
            v_hist = jnp.take(v_hist, row_index, axis=0)
        if k_scale is not None:
            k_scale = jnp.take(k_scale, row_index, axis=0)
        if v_scale is not None:
            v_scale = jnp.take(v_scale, row_index, axis=0)
    if seg_gemm:
        # dense all-rows GEMM + exact one-hot selection: restores a real
        # [B*M*G, D] x [D, U*S] GEMM shape where the gathered form is M
        # independent GEMVs per batch row
        u = k_hist.shape[0]
        onehot = (row_index[..., None] == jnp.arange(u)) \
            .astype(jnp.float32)                         # [b,m,U]
        s_all = jnp.einsum("bmhgd,ushd->bhgmus", qf,
                           k_hist.astype(jnp.float32))
        s_hist = jnp.einsum("bhgmus,bmu->bhgms", s_all, onehot)
        if k_scale is not None:
            s_hist = s_hist * jnp.moveaxis(
                k_scale, 2, 1)[:, :, None, :, None]      # [b,hkv,1,m,1]
    elif seg:
        # per-candidate history segment: same per-(m, s) dot products as
        # the shared-history einsum, just indexed per candidate
        s_hist = jnp.einsum("bmhgd,bmshd->bhgms", qf,
                            k_hist.astype(jnp.float32))
        if k_scale is not None:
            s_hist = s_hist * jnp.moveaxis(
                k_scale, 2, 1)[:, :, None, :, None]      # [b,hkv,1,m,1]
    else:
        s_hist = _segment_scores(qf, k_hist, k_scale)    # [b,hkv,g,m,S]
    if hist_ok is not None:
        s_hist = jnp.where(hist_ok, s_hist, -1e30)

    if mode == "cached":
        # self segment: query i sees exactly key i — the diagonal einsum
        s_self = jnp.einsum("bmhgd,bmhd->bhgm", qf,
                            k_cand.astype(jnp.float32))
        m_all = jnp.maximum(s_hist.max(axis=-1), s_self)
        p_hist = jnp.exp(s_hist - m_all[..., None])
        if hist_ok is not None:
            p_hist = jnp.where(hist_ok, p_hist, 0.0)
        p_self = jnp.exp(s_self - m_all)
        l = p_hist.sum(axis=-1) + p_self
        if seg_gemm:
            # scatter each candidate's probabilities back to its own pool
            # row (one nonzero u per (b, m) — exact), then contract the
            # stored values in place: one [B*M*G, U*S] x [U*S, D] GEMM
            weighted = jnp.einsum("bhgms,bmu->bhgums", p_hist, onehot)
            o = jnp.einsum("bhgums,ushd->bmhgd", weighted,
                           v_hist.astype(jnp.float32))
            if v_scale is not None:
                o = o * v_scale[:, :, :, None, None]     # [b,m,hkv,1,1]
        elif seg:
            o = jnp.einsum("bhgms,bmshd->bmhgd", p_hist,
                           v_hist.astype(jnp.float32))
            if v_scale is not None:
                o = o * v_scale[:, :, :, None, None]     # [b,m,hkv,1,1]
        else:
            o = jnp.einsum("bhgms,bshd->bmhgd", p_hist,
                           v_hist.astype(jnp.float32))
            if v_scale is not None:
                o = o * v_scale[:, None, :, None, None]
        o = o + jnp.einsum("bhgm,bmhd->bmhgd", p_self,
                           v_cand.astype(jnp.float32))
    else:                                            # extend (causal)
        s_suf = jnp.einsum("bmhgd,bshd->bhgms", qf,
                           k_cand.astype(jnp.float32))
        causal = (jnp.arange(m)[None, :] <= jnp.arange(m)[:, None])
        s_suf = jnp.where(causal[None, None, None], s_suf, -1e30)
        m_all = jnp.maximum(s_hist.max(axis=-1), s_suf.max(axis=-1))
        p_hist = jnp.exp(s_hist - m_all[..., None])
        if hist_ok is not None:
            p_hist = jnp.where(hist_ok, p_hist, 0.0)
        p_suf = jnp.exp(s_suf - m_all[..., None])
        p_suf = jnp.where(causal[None, None, None], p_suf, 0.0)
        l = p_hist.sum(axis=-1) + p_suf.sum(axis=-1)
        o = jnp.einsum("bhgms,bshd->bmhgd", p_hist,
                       v_hist.astype(jnp.float32))
        if v_scale is not None:
            o = o * v_scale[:, None, :, None, None]
        o = o + jnp.einsum("bhgms,bshd->bmhgd", p_suf,
                           v_cand.astype(jnp.float32))
    l = jnp.moveaxis(jnp.maximum(l, 1e-30), 3, 1)    # [b,m,hkv,g]
    return (o / l[..., None]).reshape(b, m, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# kernel path plumbing
# ---------------------------------------------------------------------------

def _pad_to(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _kernel_layout(q, k_hist, v_hist, k_cand, v_cand, k_scale, v_scale,
                   row_index, bq: int, bk: int):
    """Operands of both kernels from model-layout ones: block sizes fitted
    to the extents, [B,S,H,D] -> [B,H,S,D] padded to block and 128-lane
    multiples, unit scales where the pool stores no scale, and the
    per-q-block pool-row index.  Returns (padded q, k_hist, v_hist,
    k_cand, v_cand), (k_scale, v_scale), idx, bq, bk."""
    b, m, h, d = q.shape
    u, s_hist, hkv, _ = k_hist.shape
    bq = min(bq, max(8, 1 << (m - 1).bit_length()))
    bk = min(bk, max(8, 1 << (s_hist - 1).bit_length()))
    qp = _pad_to(_pad_to(jnp.swapaxes(q, 1, 2), 2, bq), 3, 128)
    khp = _pad_to(_pad_to(jnp.swapaxes(k_hist, 1, 2), 2, bk), 3, 128)
    vhp = _pad_to(_pad_to(jnp.swapaxes(v_hist, 1, 2), 2, bk), 3, 128)
    kcp = _pad_to(_pad_to(jnp.swapaxes(k_cand, 1, 2), 2, bq), 3, 128)
    vcp = _pad_to(_pad_to(jnp.swapaxes(v_cand, 1, 2), 2, bq), 3, 128)
    ones = jnp.ones((u, hkv), jnp.float32)
    ks = ones if k_scale is None else k_scale
    vs = ones if v_scale is None else v_scale
    idx = _block_rows(row_index, b, qp.shape[2], bq)
    return (qp, khp, vhp, kcp, vcp), (ks, vs), idx, bq, bk


def _block_rows(row_index, b: int, mp: int, bq: int, xp=jnp):
    """Per-q-block KV row index [B, mp // bq] (scalar prefetch): the DSO
    v2 generalization of the per-row dedup index — every q block of every
    batch row reads its own pool row's KV blocks, so a segment-packed row
    steers each candidate segment to its own user's history.  A
    per-candidate (2-D) index requires segments aligned to ``bq``
    boundaries (the packer's kernel-path contract); it is sampled at each
    q block's first candidate.  ``xp`` is ``jnp`` in the graph, ``np`` on
    the host (:func:`qblock_counts`)."""
    nq = mp // bq
    if row_index is None:
        return xp.tile(xp.arange(b, dtype=xp.int32)[:, None], (1, nq))
    if row_index.ndim == 1:
        return xp.tile(row_index.astype(xp.int32)[:, None], (1, nq))
    full = xp.pad(row_index.astype(xp.int32),
                  ((0, 0), (0, mp - row_index.shape[1])), mode="edge")
    return full[:, ::bq]


# Rows of the pointwise kernel's cached q block: a block whose sub-blocks
# all read one pool row streams that row's history once for all of them.
WIDE_Q_BLOCK = 128


def wide_qblock(m: int, sub: int) -> tuple:
    """(bq, bs) of a pointwise cached call of ``m`` candidates whose pool
    row may change every ``sub`` candidates: q blocks of the power of two
    that holds ``m``, at least 8 and at most :data:`WIDE_Q_BLOCK`, in whole
    sub-blocks of ``bs = min(sub, bq)`` rows."""
    fit = max(8, 1 << (m - 1).bit_length())
    bq = min(WIDE_Q_BLOCK, fit)
    bq = min(max(sub, bq - bq % sub), fit)
    return bq, min(sub, bq)


def qblock_uniform(row_at, n_sub: int):
    """Whether a q block's ``n_sub`` sub-blocks all read one pool row,
    ``row_at(j)`` being sub-block ``j``'s: the rule by which the pointwise
    kernel scores a block whole (scalars from its prefetched index) and by
    which :func:`qblock_counts` counts blocks (arrays of blocks)."""
    first = row_at(0)
    same = first == first
    for j in range(1, n_sub):
        same = same & (row_at(j) == first)
    return same


def qblock_counts(seg_idx: np.ndarray, sub: int) -> tuple:
    """(uniform, mixed) q blocks of a host [rows, m] packed seg index whose
    segments start on multiples of ``sub``, blocked as the pointwise
    kernel blocks a cached call of ``m`` candidates."""
    rows, m = seg_idx.shape
    bq, bs = wide_qblock(m, sub)
    mp = -(-m // bq) * bq
    blocks = _block_rows(seg_idx, rows, mp, bs, xp=np).reshape(
        rows, mp // bq, bq // bs)
    n = np.count_nonzero(qblock_uniform(lambda j: blocks[..., j],
                                        bq // bs))
    return n, blocks.shape[0] * blocks.shape[1] - n


@functools.partial(jax.jit, static_argnames=("mode", "bq", "bk",
                                             "interpret"))
def _fused_kernel_call(q, k_hist, v_hist, k_cand, v_cand, k_scale, v_scale,
                       row_index, lengths, mode: str, bq: int, bk: int,
                       interpret: bool | None):
    b, m, h, d = q.shape
    u, s_hist, hkv, _ = k_hist.shape
    scale = 1.0 / np.sqrt(d)
    (qp, khp, vhp, kcp, vcp), (ks, vs), idx, bq, bk = _kernel_layout(
        q * scale, k_hist, v_hist, k_cand, v_cand, k_scale, v_scale,
        row_index, bq, bk)
    lens = (jnp.full((u,), s_hist, jnp.int32) if lengths is None
            else jnp.asarray(lengths, jnp.int32))
    out = fused_score_kernel(idx, lens, ks, vs, qp.astype(q.dtype), khp,
                             vhp, kcp, vcp, mode=mode, sq=m, s_hist=s_hist,
                             bq=bq, bk=bk, interpret=interpret)
    return jnp.swapaxes(out[:, :, :m, :d], 1, 2)


# ---------------------------------------------------------------------------
# pointwise variant (HSTU): SiLU(s + bias) / N, no softmax
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("mode", "n_norm"))
def _hstu_jnp(q, k_hist, v_hist, k_cand, v_cand, k_scale, v_scale,
              bias_hist, bias_cand, self_bias, row_index, mode: str,
              n_norm: float):
    """The kernel's pointwise attention as one XLA graph: the history
    segment fully visible with its bias (one row per pool row, or one per
    query), the self segment the diagonal (cached) or a causal suffix with
    its bias (extend), scales folded after the dots as in the kernel."""
    b, m, h, d = q.shape
    hkv = k_cand.shape[2]
    g = h // hkv
    f32 = jnp.float32
    qf = q.astype(f32).reshape(b, m, hkv, g, d)
    seg = row_index is not None and row_index.ndim == 2
    if row_index is not None:
        # gathers run on the stored values; a 2-D (packed) index gives
        # every candidate its own pool row: [B,M,S,Hkv,D] operands
        k_hist = jnp.take(k_hist, row_index, axis=0)
        v_hist = jnp.take(v_hist, row_index, axis=0)
        bias_hist = jnp.take(bias_hist, row_index, axis=0)
        if k_scale is not None:
            k_scale = jnp.take(k_scale, row_index, axis=0)
        if v_scale is not None:
            v_scale = jnp.take(v_scale, row_index, axis=0)
    if seg:
        s_hist = jnp.einsum("bmhgd,bmshd->bhgms", qf, k_hist.astype(f32))
        if k_scale is not None:
            s_hist = s_hist * jnp.moveaxis(
                k_scale, 2, 1)[:, :, None, :, None]      # [b,hkv,1,m,1]
        s_hist = s_hist + bias_hist[:, :, 0][:, None, None]
        p_hist = jax.nn.silu(s_hist)
        o = jnp.einsum("bhgms,bmshd->bmhgd", p_hist, v_hist.astype(f32))
        if v_scale is not None:
            o = o * v_scale[:, :, :, None, None]         # [b,m,hkv,1,1]
    else:
        s_hist = _segment_scores(qf, k_hist, k_scale)    # [b,hkv,g,m,S]
        s_hist = s_hist + bias_hist[:, None, None]       # rows 1 or m
        p_hist = jax.nn.silu(s_hist)
        o = jnp.einsum("bhgms,bshd->bmhgd", p_hist, v_hist.astype(f32))
        if v_scale is not None:
            o = o * v_scale[:, None, :, None, None]
    if mode == "cached":
        s_self = jnp.einsum("bmhgd,bmhd->bhgm", qf,
                            k_cand.astype(f32)) + self_bias[0]
        o = o + jnp.einsum("bhgm,bmhd->bmhgd", jax.nn.silu(s_self),
                           v_cand.astype(f32))
    else:                                            # extend (causal)
        s_suf = jnp.einsum("bmhgd,bshd->bhgms", qf, k_cand.astype(f32))
        s_suf = s_suf + bias_cand[:, None, None]
        causal = (jnp.arange(m)[None, :] <= jnp.arange(m)[:, None])
        p_suf = jnp.where(causal[None, None, None], jax.nn.silu(s_suf), 0.0)
        o = o + jnp.einsum("bhgms,bshd->bmhgd", p_suf, v_cand.astype(f32))
    return (o / n_norm).reshape(b, m, h, d).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("mode", "bq", "bk", "n_norm",
                                             "interpret"))
def _hstu_kernel_call(q, k_hist, v_hist, k_cand, v_cand, k_scale, v_scale,
                      bias_hist, bias_cand, self_bias, row_index, mode: str,
                      bq: int, bk: int, n_norm: float,
                      interpret: bool | None):
    b, m, h, d = q.shape
    s_hist = k_hist.shape[1]
    bs = None
    if mode == "cached":
        # wide q blocks in sub-blocks of the segment alignment ``bq``
        bq, bs = wide_qblock(m, bq)
    (qp, khp, vhp, kcp, vcp), (ks, vs), idx, bq, bk = _kernel_layout(
        q, k_hist, v_hist, k_cand, v_cand, k_scale, v_scale, row_index,
        bq, bk)
    if bs is not None:
        idx = _block_rows(row_index, b, qp.shape[2], bs)
    bias_hist = _pad_to(jnp.asarray(bias_hist, jnp.float32), 2, bk)
    if bias_hist.shape[1] > 1:
        bias_hist = _pad_to(bias_hist, 1, bq)
    if bias_cand is not None:
        bias_cand = _pad_to(_pad_to(jnp.asarray(bias_cand, jnp.float32),
                                    1, bq), 2, bq)
    out = hstu_score_kernel(
        idx, ks, vs, jnp.asarray(self_bias, jnp.float32).reshape(1), qp,
        khp, vhp, bias_hist, kcp, vcp, bias_cand, mode=mode, sq=m,
        s_hist=s_hist, n_norm=n_norm, bq=bq, bk=bk, bs=bs,
        interpret=interpret)
    return jnp.swapaxes(out[:, :, :m, :d], 1, 2)


def time_bucket(dt, max_bucket: int):
    """HSTU's time bucket of int32 gaps ``dt``: floor(ln |dt| / 0.301),
    gaps under a second in bucket 0, capped at ``max_bucket``."""
    gap = jnp.maximum(jnp.abs(dt), 1).astype(jnp.float32)
    b = jnp.floor(jnp.log(gap) / TIME_BUCKET_WIDTH)
    return jnp.minimum(b, max_bucket).astype(jnp.int32)


def _position_bias(w_pos, n_q: int, n_k: int, offset: int):
    """[n_q, n_k] ``w_pos[clip(offset + i - j, 0, len - 1)]``, a Toeplitz
    matrix, without a gather (an XLA gather runs one index at a time on the
    TPU): the offsets it reads are one run of the table, sliced statically
    (clipped ends repeat the end entries), whose sliding windows come from
    a broadcast read with a row stride one longer than the run."""
    p = w_pos.shape[0]
    run = n_q + n_k - 1                   # offsets offset-(n_k-1) .. +n_q-1
    lo = offset - (n_k - 1)
    z = jnp.concatenate([
        jnp.full((max(0, -lo),), w_pos[0]),
        w_pos[max(lo, 0):min(lo + run, p)],
        jnp.full((max(0, lo + run - p),), w_pos[p - 1])])
    # windows of the reversed run: row r holds zr[r : r + n_k]
    zr = z[::-1]
    flat = jnp.broadcast_to(zr, (n_q + 1, run)).reshape(-1)
    windows = flat[:n_q * (run + 1)].reshape(n_q, run + 1)[:, :n_k]
    return windows[::-1]


@functools.partial(jax.jit, static_argnames=("offset", "path",
                                             "interpret"))
def hstu_relative_bias(w_pos, w_time, t_q, t_k, *, offset: int,
                       path: str = "auto", interpret=None):
    """HSTU's relative attention bias [B, Q, K] f32 of queries at
    positions ``offset + i`` with times ``t_q`` [B, Q] over keys at
    positions ``j`` with times ``t_k`` [B, K]:
    ``w_pos[clip(offset + i - j, 0, len - 1)] + w_time[bucket(t_q[i] -
    t_k[j])]``.  The position part is a Toeplitz matrix of row slices; the
    time part a lane-gather kernel (``path="kernel"``), or an XLA gather
    (``"jnp"``, the CPU's; ``"auto"`` picks as the attention does).  The
    kernel's table is one 128-lane row: int32 gaps never pass bucket 71
    (ln 2**31 / 0.301 < 72), so its cap at 127 reads as the table's."""
    f32 = jnp.float32
    b, n_q = t_q.shape
    n_k = t_k.shape[1]
    w_time = jnp.asarray(w_time, f32)
    max_bucket = w_time.shape[0] - 1
    pos = _position_bias(jnp.asarray(w_pos, f32), n_q, n_k, offset)
    if path == "auto":
        path = _auto_path()
    if path == "jnp":
        tb = time_bucket(t_q[:, :, None] - t_k[:, None, :], max_bucket)
        return pos[None] + w_time[tb]
    if path != "kernel":
        raise ValueError(f"path must be auto|kernel|jnp, got {path!r}")
    bq = min(256, max(8, 1 << (n_q - 1).bit_length()))
    table = _pad_to(w_time[:LANES][None], 1, LANES)
    out = time_bias_kernel(
        _pad_to(t_q.astype(jnp.int32)[:, :, None], 1, bq),
        _pad_to(t_k.astype(jnp.int32)[:, None, :], 2, LANES), table,
        _pad_to(_pad_to(pos, 0, bq), 1, LANES),
        max_bucket=min(max_bucket, LANES - 1), bq=bq, interpret=interpret)
    return out[:, :n_q, :n_k]


def _pointwise_attention(q, k_hist, v_hist, k_cand, v_cand, bias_hist, *,
                         mode: str, n_norm: float, self_bias=None,
                         bias_cand=None, k_scale=None, v_scale=None,
                         row_index=None, path: str = "auto", bk: int = 128,
                         interpret=None):
    u, hkv = k_hist.shape[0], k_hist.shape[2]
    ks = _norm_scale(k_scale, u, hkv)
    vs = _norm_scale(v_scale, u, hkv)
    if self_bias is None:
        self_bias = jnp.zeros((1,), jnp.float32)
    self_bias = jnp.asarray(self_bias, jnp.float32).reshape(1)
    path, bq = _route(q, k_hist, row_index, mode, path)
    if path == "kernel":
        return _hstu_kernel_call(q, k_hist, v_hist, k_cand, v_cand, ks, vs,
                                 bias_hist, bias_cand, self_bias, row_index,
                                 mode, bq, bk, float(n_norm), interpret)
    return _hstu_jnp(q, k_hist, v_hist, k_cand, v_cand, ks, vs, bias_hist,
                     bias_cand, self_bias, row_index, mode, float(n_norm))


def hstu_cached_attention(q, k_hist, v_hist, k_cand, v_cand, bias_hist,
                          self_bias, *, n_norm: float, k_scale=None,
                          v_scale=None, row_index=None, path: str = "auto",
                          bk: int = 128, interpret=None):
    """HSTU candidate scoring against pooled history K/V: every candidate
    adds ``SiLU(q.k_j + bias_hist[row, j]) v_j`` over its pool row's
    history and ``SiLU(q.k_self + self_bias) v_self``, over ``n_norm``.

    Operands as :func:`fused_cached_attention` (1-D dedup and 2-D packed
    ``row_index`` welcome); ``bias_hist`` [U, 1, S] f32 (one row per pool
    row, shared by its candidates and heads); ``self_bias`` a scalar."""
    return _pointwise_attention(
        q, k_hist, v_hist, k_cand, v_cand, bias_hist, mode="cached",
        n_norm=n_norm, self_bias=self_bias, k_scale=k_scale,
        v_scale=v_scale, row_index=row_index, path=path, bk=bk,
        interpret=interpret)


def hstu_extend_attention(q, k_prefix, v_prefix, k_suffix, v_suffix,
                          bias_prefix, bias_suffix, *, n_norm: float,
                          k_scale=None, v_scale=None, path: str = "auto",
                          bk: int = 128, interpret=None):
    """HSTU causal suffix attention against pooled prefix K/V:
    ``bias_prefix`` [B, M, P] and ``bias_suffix`` [B, M, M] f32, one row
    per suffix query (entries above the diagonal are never read)."""
    return _pointwise_attention(
        q, k_prefix, v_prefix, k_suffix, v_suffix, bias_prefix,
        mode="extend", n_norm=n_norm, bias_cand=bias_suffix,
        k_scale=k_scale, v_scale=v_scale, path=path, bk=bk,
        interpret=interpret)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def _route(q, k_hist, row_index, mode: str, path: str):
    """(path, bq) of one call: a 2-D (segment-packed) index runs q blocks
    of :data:`PACK_ALIGN` rows; ``auto`` is the kernel where it compiles
    for real."""
    bq = 128
    if row_index is not None and row_index.ndim == 2:
        if mode != "cached":
            raise ValueError("per-candidate (segment-packed) row_index only "
                             "applies to cached candidate scoring")
        if row_index.shape != q.shape[:2]:
            raise ValueError(f"2-D row_index must be [B, M] = {q.shape[:2]}, "
                             f"got {row_index.shape}")
        bq = PACK_ALIGN
    if k_hist.shape[1] == 0:
        raise ValueError("fused attention needs a non-empty history/prefix "
                         "segment (degenerate cases route to the framework "
                         "impls in core/sumi.py)")
    if path == "auto":
        path = _auto_path()
    if path not in ("kernel", "jnp"):
        raise ValueError(f"path must be auto|kernel|jnp, got {path!r}")
    return path, bq


def _fused_attention(q, k_hist, v_hist, k_cand, v_cand, *, mode: str,
                     k_scale=None, v_scale=None, row_index=None,
                     lengths=None, temperature=None, path: str = "auto",
                     interpret=None):
    if temperature is not None:
        q = q / jnp.asarray(temperature, q.dtype)
    u, hkv = k_hist.shape[0], k_hist.shape[2]
    ks = _norm_scale(k_scale, u, hkv)
    vs = _norm_scale(v_scale, u, hkv)
    path, bq = _route(q, k_hist, row_index, mode, path)
    if path == "kernel":
        return _fused_kernel_call(q, k_hist, v_hist, k_cand, v_cand,
                                  ks, vs, row_index, lengths, mode, bq,
                                  128, interpret)
    return _fused_jnp(q, k_hist, v_hist, k_cand, v_cand, ks, vs,
                      row_index, lengths, mode)


def fused_cached_attention(q, k_hist, v_hist, k_cand, v_cand, *,
                           k_scale=None, v_scale=None, row_index=None,
                           temperature=None, path: str = "auto",
                           interpret=None):
    """Candidate-only SUMI attention against pooled history K/V.

    ``q``/``k_cand``/``v_cand`` [B,M,H(kv),D] fresh candidate projections;
    ``k_hist``/``v_hist`` [U,S,Hkv,D] pool-stored values (int8/bf16/
    native) with optional [U,1,Hkv,1] ``k_scale``/``v_scale`` and a [B]
    ``row_index`` selecting each batch row's pool row (KV-row dedup)."""
    return _fused_attention(q, k_hist, v_hist, k_cand, v_cand,
                            mode="cached", k_scale=k_scale, v_scale=v_scale,
                            row_index=row_index, temperature=temperature,
                            path=path, interpret=interpret)


def fused_decode_attention(q, k_hist, v_hist, k_cand, v_cand, lengths, *,
                           k_scale=None, v_scale=None, row_index=None,
                           temperature=None, path: str = "auto",
                           interpret=None):
    """Generative-decode candidate scoring against padded beam caches.

    Cached-mode fused attention with a per-pool-row valid-prefix bound:
    ``k_hist``/``v_hist`` [U,S,Hkv,D] are PADDED growing caches (history
    + appended generated tokens, S = s0 + max_steps) in the pool's stored
    precision, and ``lengths`` [U] int32 bounds each row's valid prefix.
    Candidates/queries follow :func:`fused_cached_attention` conventions,
    including 1-D dedup and 2-D segment-packed ``row_index`` steering
    (lengths are gathered per candidate through the same index).  At
    ``lengths == S`` this is bitwise :func:`fused_cached_attention`."""
    return _fused_attention(q, k_hist, v_hist, k_cand, v_cand,
                            mode="cached", k_scale=k_scale, v_scale=v_scale,
                            row_index=row_index, lengths=lengths,
                            temperature=temperature, path=path,
                            interpret=interpret)


def fused_extend_attention(q, k_prefix, v_prefix, k_suffix, v_suffix, *,
                           k_scale=None, v_scale=None, row_index=None,
                           temperature=None, path: str = "auto",
                           interpret=None):
    """Causal suffix attention against pooled prefix K/V (incremental
    history extension).  Same operand conventions as
    :func:`fused_cached_attention`; query row i sits at absolute position
    ``P + i`` and the suffix segment is causal within itself."""
    return _fused_attention(q, k_prefix, v_prefix, k_suffix, v_suffix,
                            mode="extend", k_scale=k_scale, v_scale=v_scale,
                            row_index=row_index, temperature=temperature,
                            path=path, interpret=interpret)


def block_epilogue(x, o, attn_params, norm_params, ffn_params, cfg, *,
                   path: str = "auto", interpret=None):
    """Per-layer epilogue of one fused block step: out-projection +
    residual + norm + FFN + residual.

    On TPU (``path="auto"``) the norm + FFN chain reuses the
    ``kernels/fused_ffn`` Pallas kernel (norm folded into the first
    matmul, f32 VMEM accumulator); elsewhere it is the exact framework
    composition, so the jnp fused path stays bitwise-aligned with the
    chunked impl's epilogue."""
    from repro.models import layers as L
    from repro.models.ffn import ffn_apply

    x = x + jnp.einsum("bshk,hkd->bsd", o, attn_params["wo"])
    if path == "auto":
        path = _auto_path()
    if path == "kernel" and cfg.norm == "rmsnorm":
        from repro.kernels.fused_ffn import ops as ffn_ops
        return x + ffn_ops.fused_ffn(x, ffn_params,
                                     activation=cfg.activation,
                                     norm_scale=norm_params["scale"],
                                     interpret=interpret)
    h2 = L.apply_norm(cfg, norm_params, x)
    return x + ffn_apply(ffn_params, h2, cfg, impl="xla")
