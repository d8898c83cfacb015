"""SUMI — single user, multiple items: FLAME's request paradigm.

A GR ranking request carries one user history (length n) and M candidate
items.  All M candidates are scored in ONE forward pass by concatenating them
after the history and applying the SUMI mask (candidates attend to history
and themselves, never to each other) — the HSTU-style parallel-prediction
trick the paper bakes into its mask-aware flash-attention plug-in.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention as A


def assemble(history_emb: jnp.ndarray, cand_emb: jnp.ndarray
             ) -> Tuple[jnp.ndarray, int]:
    """[B,n,d] + [B,M,d] -> ([B,n+M,d], n_history)."""
    return jnp.concatenate([history_emb, cand_emb], axis=1), history_emb.shape[1]


def split_candidates(x: jnp.ndarray, n_history: int) -> jnp.ndarray:
    """[B,n+M,d] -> candidate outputs [B,M,d]."""
    return x[:, n_history:]


def sumi_attention(q, k, v, n_history: int, *, impl: str = "reference",
                   temperature=None):
    """Mask-aware attention under the SUMI mask.  q/k/v [B,S,H,D]."""
    if temperature is not None:
        q = q / jnp.asarray(temperature, q.dtype)
    return A.attention(q, k, v, "sumi", impl=impl, n_history=n_history)


def _dequant_gather(k, v, k_scale, v_scale, row_index, dtype):
    """Materialize pool-stored operands for the framework (non-fused)
    impls: dequantize + per-row gather — the exact sequence the FKE
    oracle defines (one implementation, so the framework impls can never
    drift from what the fused paths are gated against)."""
    from repro.kernels.fused_score.ref import _prep
    return _prep(k, v, k_scale, v_scale, row_index, dtype)


def _segment_packed_attention(q, k_hist, v_hist, k_cand, v_cand, seg):
    """Cached-candidate SUMI attention for a segment-packed row (framework
    impls; ``impl="fused"`` handles the 2-D index natively in ops.py).

    ``seg`` [B, M] maps every candidate to its user's (dequantized) pool
    row in ``k_hist``/``v_hist`` [U, S, Hkv, D].  The computation mirrors
    ``models/attention.py::reference_attention`` op for op — einsum scores
    scaled by 1/sqrt(D), -1e30 mask fill, softmax over the [M, S+M] axis,
    one output reduction over S+M — with the history operands gathered per
    CANDIDATE instead of shared per row.  Masked positions (other
    segments' candidates) contribute exact zeros, and every reduction has
    the same length and per-element operand values as the unpacked
    shared-KV row, so packed scores are bitwise-identical to unpacked
    dispatches wherever the framework impl routes to the reference path
    (all serving-scale cached executors do; asserted in
    tests/test_dso_v2.py)."""
    b, m, h, d = q.shape
    hkv = k_cand.shape[2]
    g = h // hkv
    s = k_hist.shape[1]
    kh = jnp.take(k_hist, seg, axis=0)             # [B, M, S, Hkv, D]
    vh = jnp.take(v_hist, seg, axis=0)
    qf = q.astype(jnp.float32).reshape(b, m, hkv, g, d)
    s_hist = jnp.einsum("bmhgd,bmshd->bhgms", qf,
                        kh.astype(jnp.float32)) / np.sqrt(d)
    s_cand = jnp.einsum("bmhgd,bkhd->bhgmk", qf,
                        k_cand.astype(jnp.float32)) / np.sqrt(d)
    scores = jnp.concatenate([s_hist, s_cand], axis=-1)   # [b,hkv,g,m,S+M]
    mask = A.make_mask(m, s + m, "sumi", n_history=s, q_offset=s)
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    vc = jnp.broadcast_to(v_cand.astype(jnp.float32)[:, None],
                          (b, m, m, hkv, d))
    v_all = jnp.concatenate([vh.astype(jnp.float32), vc], axis=2)
    o = jnp.einsum("bhgmk,bmkhd->bmhgd", w, v_all)
    return o.reshape(b, m, h, d).astype(q.dtype)


def cached_candidate_attention(q, k_hist, v_hist, k_cand, v_cand, *,
                               impl: str = "reference", temperature=None,
                               k_scale=None, v_scale=None, row_index=None):
    """Candidate-only SUMI attention against cached per-layer history K/V.

    The SUMI mask makes the history prefix self-contained (history rows are
    causal among themselves and never see candidates), so the history-side
    K/V depend only on the user history and can be reused across requests.
    Here ``q``/``k_cand``/``v_cand`` are [B,M,...] candidate projections and
    ``k_hist``/``v_hist`` [B,n_history,...] come from a cached
    ``encode_history`` pass; query row i sits at absolute KV position
    ``n_history + i`` (its own key), which every impl honors via
    ``q_offset``.  Output is bit-for-bit the candidate slice of the
    monolithic SUMI pass under the reference impl (allclose for the
    block-reordered chunked/pallas/fused impls).

    FKE operand extensions: ``k_hist``/``v_hist`` may arrive in the
    history pool's *stored* precision (int8/bf16) with per-(row, head)
    ``k_scale``/``v_scale``, and ``row_index`` [B] selects each batch
    row's pool row (the DSO's KV-row dedup).  ``impl="fused"`` consumes
    them in-kernel (no dequant / gather / concat materialization); every
    other impl materializes the framework operands first.

    DSO v2 segment packing: ``row_index`` may instead be ``[B, M]`` — a
    per-CANDIDATE pool-row index, so one batch row can carry candidate
    segments of *different* users (each candidate attends to its own
    user's history + itself; candidates never see each other under SUMI,
    so packing is exact by construction).  ``impl="fused"`` gathers the
    stored rows per candidate (jnp path) or steers per-q-block KV reads
    through scalar prefetch (kernel path); the framework impls run
    :func:`_segment_packed_attention` — the reference computation with
    per-candidate gathered history, bitwise-identical to the unpacked
    shared-KV dispatch."""
    if temperature is not None:
        q = q / jnp.asarray(temperature, q.dtype)
    if impl == "fused":
        from repro.kernels.fused_score import ops as fs_ops
        return fs_ops.fused_cached_attention(
            q, k_hist, v_hist, k_cand, v_cand, k_scale=k_scale,
            v_scale=v_scale, row_index=row_index)
    if row_index is not None and jnp.ndim(row_index) == 2:
        k_hist, v_hist = _dequant_gather(k_hist, v_hist, k_scale, v_scale,
                                         None, q.dtype)
        return _segment_packed_attention(q, k_hist, v_hist, k_cand, v_cand,
                                         jnp.asarray(row_index, jnp.int32))
    if k_scale is not None or v_scale is not None or row_index is not None \
            or k_hist.dtype != q.dtype:
        k_hist, v_hist = _dequant_gather(k_hist, v_hist, k_scale, v_scale,
                                         row_index, q.dtype)
    n_history = k_hist.shape[1]
    k = jnp.concatenate([k_hist, k_cand], axis=1)
    v = jnp.concatenate([v_hist, v_cand], axis=1)
    return A.attention(q, k, v, "sumi", impl=impl, n_history=n_history,
                       q_offset=n_history)


def _kernel_decode_attention(q, k_hist, v_hist, k_cand, v_cand, lengths):
    """Generative-decode scoring via the seed's flash-decode kernel
    (``kernels/flash_decode``): each candidate's own K/V is written into a
    private copy of its cache row at position ``lengths`` and the kernel
    runs single-token decode attention with ``lengths + 1`` — the
    "decode step = score_candidates(M=1) + KV append" identity made
    literal.  ``k_hist``/``v_hist`` arrive PRE-GATHERED per candidate
    ([B,M,S,Hkv,D]) with ``lengths`` [B,M]."""
    from repro.kernels.flash_decode.ops import flash_decode
    b, m, h, d = q.shape
    s = k_hist.shape[2]
    hkv = k_cand.shape[2]
    # one spare column so a full (unpadded) cache still has a self slot
    kh = jnp.pad(k_hist, ((0, 0), (0, 0), (0, 1), (0, 0), (0, 0))
                 ).reshape(b * m, s + 1, hkv, d)
    vh = jnp.pad(v_hist, ((0, 0), (0, 0), (0, 1), (0, 0), (0, 0))
                 ).reshape(b * m, s + 1, hkv, d)
    lens = lengths.reshape(b * m).astype(jnp.int32)
    put = jax.vmap(lambda c, t, i: jax.lax.dynamic_update_slice(
        c, t, (i, 0, 0)))
    kh = put(kh, k_cand.reshape(b * m, 1, hkv, d), lens)
    vh = put(vh, v_cand.reshape(b * m, 1, hkv, d), lens)
    o = flash_decode(q.reshape(b * m, h, d), kh, vh, lens + 1)
    return o.reshape(b, m, h, d)


def decode_candidate_attention(q, k_hist, v_hist, k_cand, v_cand, lengths, *,
                               impl: str = "reference", temperature=None,
                               k_scale=None, v_scale=None, row_index=None):
    """Generative-decode SUMI attention against a padded, growing cache.

    Same contract as :func:`cached_candidate_attention` except the history
    operands are PRE-PADDED beam caches whose valid prefix per row is
    ``lengths`` (int32): candidate m attends to cache positions ``<
    lengths`` plus itself, never to other candidates or cache padding.
    Because masked positions contribute exact softmax zeros, a padded
    cache scores bitwise-identically to the tight cache — and at
    ``lengths == S`` with no padding this is op-for-op
    :func:`cached_candidate_attention` (asserted in
    tests/test_decode_serving.py), so one greedy decode step IS
    ``score_candidates`` over the vocab.

    ``row_index`` [B, M] is the DSO v2 packed-decode steer: ``k_hist`` /
    ``v_hist`` are then [U,S,Hkv,D] stacked beam caches with ``lengths``
    [U], and every candidate gathers its own beam's cache + valid length
    (same placement-invariance argument as
    :func:`_segment_packed_attention`).  ``impl="pallas"`` routes the
    flash-decode kernel (self K/V written into the cache row, ``lengths +
    1``); ``impl="fused"`` routes the FKE v2 lengths-masked two-segment
    kernel (``fused_score.ops.fused_decode_attention``), which consumes
    the STORED int8/bf16 cache plus scales directly — dequant folded into
    the score/accumulator multiplies, no gather/concat materialization;
    every other impl runs the reference-structured jnp formulation below
    (exact at serving scale: the chunked scoring path routes to reference
    for decode-sized shapes)."""
    if temperature is not None:
        q = q / jnp.asarray(temperature, q.dtype)
    if impl == "fused":
        from repro.kernels.fused_score import ops as fs_ops
        return fs_ops.fused_decode_attention(
            q, k_hist, v_hist, k_cand, v_cand, lengths, k_scale=k_scale,
            v_scale=v_scale, row_index=row_index)
    if k_scale is not None or v_scale is not None \
            or k_hist.dtype != q.dtype:
        k_hist, v_hist = _dequant_gather(k_hist, v_hist, k_scale, v_scale,
                                         None, q.dtype)
    b, m, h, d = q.shape
    s = k_hist.shape[1]
    hkv = k_cand.shape[2]
    g = h // hkv
    lengths = jnp.asarray(lengths, jnp.int32)
    if row_index is not None:
        if jnp.ndim(row_index) != 2:
            raise ValueError("decode attention row_index must be [B, M] "
                             "(per-candidate beam steer) when given")
        seg = jnp.asarray(row_index, jnp.int32)
        kh = jnp.take(k_hist, seg, axis=0)         # [B, M, S, Hkv, D]
        vh = jnp.take(v_hist, seg, axis=0)
        lens = jnp.take(lengths, seg)              # [B, M]
        if impl == "pallas":
            return _kernel_decode_attention(q, kh, vh, k_cand, v_cand, lens)
        qf = q.astype(jnp.float32).reshape(b, m, hkv, g, d)
        s_hist = jnp.einsum("bmhgd,bmshd->bhgms", qf,
                            kh.astype(jnp.float32)) / np.sqrt(d)
        s_cand = jnp.einsum("bmhgd,bkhd->bhgmk", qf,
                            k_cand.astype(jnp.float32)) / np.sqrt(d)
        scores = jnp.concatenate([s_hist, s_cand], axis=-1)
        base = A.make_mask(m, s + m, "sumi", n_history=s, q_offset=s)
        hist_ok = jnp.arange(s)[None, None, :] < lens[:, :, None]  # [B,M,S]
        ok = jnp.concatenate(
            [hist_ok, jnp.ones((b, m, m), bool)], axis=-1)
        mask = base[None, None, None] & ok[:, None, None]
        scores = jnp.where(mask, scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1)
        vc = jnp.broadcast_to(v_cand.astype(jnp.float32)[:, None],
                              (b, m, m, hkv, d))
        v_all = jnp.concatenate([vh.astype(jnp.float32), vc], axis=2)
        o = jnp.einsum("bhgmk,bmkhd->bmhgd", w, v_all)
        return o.reshape(b, m, h, d).astype(q.dtype)
    if impl == "pallas":
        kh = jnp.broadcast_to(k_hist[:, None], (b, m) + k_hist.shape[1:])
        vh = jnp.broadcast_to(v_hist[:, None], (b, m) + v_hist.shape[1:])
        lens = jnp.broadcast_to(lengths[:, None], (b, m))
        return _kernel_decode_attention(q, kh, vh, k_cand, v_cand, lens)
    # per-row cache: mirror cached_candidate_attention's reference route
    # (concat + reference_attention ops) with the valid-length mask folded
    # into the SUMI mask — at lengths == S the fold is the identity, so
    # this is bitwise the score_candidates attention
    k = jnp.concatenate([k_hist, k_cand], axis=1)
    v = jnp.concatenate([v_hist, v_cand], axis=1)
    qf = q.astype(jnp.float32).reshape(b, m, hkv, g, d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qf,
                        k.astype(jnp.float32)) / np.sqrt(d)
    base = A.make_mask(m, s + m, "sumi", n_history=s, q_offset=s)
    ok = jnp.concatenate(
        [jnp.arange(s)[None, :] < lengths[:, None],
         jnp.ones((b, m), bool)], axis=-1)                      # [B, S+M]
    mask = base[None, None, None] & ok[:, None, None, None]
    scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", w, v.astype(jnp.float32))
    return o.reshape(b, m, h, d).astype(q.dtype)


def extend_attention(q, k_prefix, v_prefix, k_suffix, v_suffix, *,
                     impl: str = "reference", temperature=None,
                     k_scale=None, v_scale=None, row_index=None):
    """Causal suffix attention against cached prefix K/V (incremental
    history extension, the MTServe "extend a cached prefix" step).

    ``q``/``k_suffix``/``v_suffix`` are [B,S_suf,...] projections of the
    history positions being (re-)encoded; ``k_prefix``/``v_prefix``
    [B,P,...] come from a cached ``encode_history`` pass whose first ``P``
    positions are trusted unchanged.  Query row i sits at absolute position
    ``P + i`` and attends causally over the concatenated KV axis — exactly
    the rows a full re-encode would attend to, so the output is bit-for-bit
    the suffix slice of a full history encode under the reference impl
    (chunked routes there at serving scales).  The FKE operand extensions
    (``k_scale``/``v_scale``/``row_index``) follow
    :func:`cached_candidate_attention`; a zero-length prefix degenerates
    to plain causal attention and routes to the framework impls.  Suffix
    positions are causally ordered, so segment packing does not apply —
    a per-candidate (2-D) ``row_index`` is rejected."""
    if row_index is not None and jnp.ndim(row_index) == 2:
        raise ValueError("extend attention is causal within the suffix — "
                         "segment-packed (per-candidate) row_index only "
                         "applies to cached candidate scoring")
    if temperature is not None:
        q = q / jnp.asarray(temperature, q.dtype)
    if impl == "fused" and k_prefix.shape[1] > 0:
        from repro.kernels.fused_score import ops as fs_ops
        return fs_ops.fused_extend_attention(
            q, k_prefix, v_prefix, k_suffix, v_suffix, k_scale=k_scale,
            v_scale=v_scale, row_index=row_index)
    if k_scale is not None or v_scale is not None or row_index is not None \
            or k_prefix.dtype != q.dtype:
        k_prefix, v_prefix = _dequant_gather(k_prefix, v_prefix, k_scale,
                                             v_scale, row_index, q.dtype)
    if impl == "fused":
        impl = "chunked"                     # empty prefix: plain causal
    p0 = k_prefix.shape[1]
    k = jnp.concatenate([k_prefix, k_suffix], axis=1)
    v = jnp.concatenate([v_prefix, v_suffix], axis=1)
    return A.attention(q, k, v, "causal", impl=impl, q_offset=p0)


def sumi_mask(n_history: int, n_candidates: int) -> jnp.ndarray:
    """Dense boolean mask (for tests / the unfused baseline)."""
    s = n_history + n_candidates
    return A.make_mask(s, s, "sumi", n_history=n_history)


def flops_per_request(n_history: int, n_candidates: int, n_blocks: int,
                      layers_per_block: int, d_model: int, d_ff: int) -> float:
    """Analytic FLOPs of one SUMI forward (paper Table 2 reproduction)."""
    s_block = n_history // n_blocks + n_candidates
    per_tok_proj = 2 * (4 * d_model * d_model + 2 * d_model * d_ff)
    # attention scores+values; SUMI mask: candidates only see history+self
    n_hist_b = n_history // n_blocks
    attn_pairs = n_hist_b * (n_hist_b + 1) / 2 + n_candidates * (n_hist_b + 1)
    per_layer = s_block * per_tok_proj + 2 * 2 * attn_pairs * d_model
    return n_blocks * layers_per_block * per_layer


def cached_flops_per_request(n_history: int, n_candidates: int, n_blocks: int,
                             layers_per_block: int, d_model: int,
                             d_ff: int) -> float:
    """Analytic FLOPs of a candidate-only pass against cached history K/V:
    projections/FFN run over M tokens instead of n_history + M, and the
    attention pairs lose the causal history-history triangle."""
    per_tok_proj = 2 * (4 * d_model * d_model + 2 * d_model * d_ff)
    n_hist_b = n_history // n_blocks
    attn_pairs = n_candidates * (n_hist_b + 1)
    per_layer = n_candidates * per_tok_proj + 2 * 2 * attn_pairs * d_model
    return n_blocks * layers_per_block * per_layer


def pointwise_flops_per_request(n_history: int, n_candidates: int,
                                n_layers: int, d_model: int,
                                attn_width: int) -> float:
    """Analytic FLOPs of one pointwise-attention (HSTU) forward under the
    SUMI / M-FALCON mask: per token and layer the ``d -> 4 * attn_width``
    and ``attn_width -> d`` projections, QK and AV over the causal history
    pairs and each candidate's history plus itself; no FFN."""
    per_tok_proj = 2 * (d_model * 4 * attn_width + attn_width * d_model)
    attn_pairs = n_history * (n_history + 1) / 2 \
        + n_candidates * (n_history + 1)
    return n_layers * ((n_history + n_candidates) * per_tok_proj
                       + 2 * 2 * attn_pairs * attn_width)
