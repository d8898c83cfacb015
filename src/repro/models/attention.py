"""GQA attention with the mask modes FLAME needs.

Mask modes
----------
``causal``   standard autoregressive
``full``     bidirectional (encoder / cross-attention)
``sliding``  causal within ``window``
``sumi``     FLAME's single-user-multi-items mask: the first ``n_history``
             positions are causal among themselves; the remaining candidate
             positions attend to all history and to themselves only —
             candidates never see each other (HSTU-style parallel scoring).

Implementations
---------------
``reference``  materialized scores — oracle + small shapes only
``chunked``    flash-style online softmax over KV chunks in pure jnp; used by
               the dry-run (no O(S^2) temporaries).  Sliding mode slices only
               the in-window KV chunks, so FLOPs scale with S*W, not S^2.
``pallas``     the mask-aware flash-attention Pallas kernel
               (kernels/flash_attention) — TPU target.
``fused``      the FKE candidate-scoring engine (kernels/fused_score): the
               cached-candidate SUMI path runs a two-segment fused kernel
               that can read quantized pool KV and the DSO's dedup row
               index directly; other mask/offset combinations fall back to
               ``chunked``.
"""
from __future__ import annotations

import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import flags
from repro.models import layers as L


# ---------------------------------------------------------------------------
# masks
# ---------------------------------------------------------------------------

def mask_value(q_pos, k_pos, mode: str, *, window: int = 0, n_history: int = 0):
    """Boolean mask (True = attend) broadcast over q_pos x k_pos index arrays."""
    if mode == "full":
        return jnp.ones(jnp.broadcast_shapes(q_pos.shape, k_pos.shape), bool)
    if mode == "causal":
        return k_pos <= q_pos
    if mode == "sliding":
        return (k_pos <= q_pos) & (q_pos - k_pos < window)
    if mode == "sumi":
        q_is_hist = q_pos < n_history
        k_is_hist = k_pos < n_history
        hist_mask = k_pos <= q_pos                      # causal (k<=q<n_hist => k in history)
        cand_mask = k_is_hist | (k_pos == q_pos)        # history + self only
        return jnp.where(q_is_hist, hist_mask, cand_mask)
    raise ValueError(mode)


def make_mask(s_q: int, s_k: int, mode: str, *, window: int = 0,
              n_history: int = 0, q_offset: int = 0):
    q = jnp.arange(s_q)[:, None] + q_offset
    k = jnp.arange(s_k)[None, :]
    return mask_value(q, k, mode, window=window, n_history=n_history)


# ---------------------------------------------------------------------------
# projections
# ---------------------------------------------------------------------------

def qkv_init(key, cfg, stacked: int = 0, d_model: Optional[int] = None):
    d = d_model or cfg.d_model
    hd = cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": L.dense_init(ks[0], (d, cfg.n_heads, hd), ("embed", "heads", None),
                           stacked=stacked, fan_in_axes=(0,)),
        "wk": L.dense_init(ks[1], (d, cfg.n_kv_heads, hd), ("embed", "kv_heads", None),
                           stacked=stacked, fan_in_axes=(0,)),
        "wv": L.dense_init(ks[2], (d, cfg.n_kv_heads, hd), ("embed", "kv_heads", None),
                           stacked=stacked, fan_in_axes=(0,)),
        "wo": L.dense_init(ks[3], (cfg.n_heads, hd, d), ("heads", None, "embed"),
                           stacked=stacked, fan_in_axes=(0, 1)),
    }
    if cfg.qkv_bias:
        p["bq"] = L.zeros_init((cfg.n_heads, hd), ("heads", None), stacked=stacked)
        p["bk"] = L.zeros_init((cfg.n_kv_heads, hd), ("kv_heads", None), stacked=stacked)
        p["bv"] = L.zeros_init((cfg.n_kv_heads, hd), ("kv_heads", None), stacked=stacked)
    return p


def project_qkv(params, x, cfg, positions):
    """x [B,S,d] -> q [B,S,H,D], k/v [B,S,Hkv,D], RoPE applied."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = L.rope(q, positions, cfg.rope_theta)
    k = L.rope(k, positions, cfg.rope_theta)
    return q, k, v


def project_out(params, o):
    """o [B,S,H,D] -> [B,S,d]."""
    return jnp.einsum("bshk,hkd->bsd", o, params["wo"])


# ---------------------------------------------------------------------------
# reference attention (materialized)
# ---------------------------------------------------------------------------

def reference_attention(q, k, v, mode: str, *, window: int = 0,
                        n_history: int = 0, q_offset: int = 0,
                        temperature=None):
    """q [B,Sq,H,D], k/v [B,Sk,Hkv,D] -> [B,Sq,H,D].  GQA via head groups."""
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qf = q.astype(jnp.float32).reshape(b, sq, hkv, g, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kf) / np.sqrt(d)
    if temperature is not None:
        scores = scores / temperature
    mask = make_mask(sq, k.shape[1], mode, window=window,
                     n_history=n_history, q_offset=q_offset)
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", w, vf)
    return o.reshape(b, sq, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# chunked flash-style attention (pure jnp, no O(S^2) memory)
# ---------------------------------------------------------------------------

def _visible_kv_blocks(mode: str, qi: int, *, q_chunk: int, k_chunk: int,
                       nk: int, sk: int, n_history: int,
                       q_offset: int) -> List[int]:
    """KV chunk indices a q chunk can see under a static mask (exact block
    skip, mirroring the pallas kernel's grid trimming).

    ``causal`` (and ``sumi`` with ``q_offset == 0``, whose candidate rows
    attend only at-or-below their own position): chunks up to the one holding
    the q chunk's last diagonal element.  ``sumi`` with ``q_offset > 0``
    (every query is a candidate): the history chunks plus the chunk(s)
    holding the queries' own keys — per-candidate work is O(n_history +
    q_chunk), independent of where the candidate block sits.
    """
    hi = min(q_offset + (qi + 1) * q_chunk, sk)        # exclusive col bound
    n_vis = min(nk, max(1, -(-hi // k_chunk)))
    if mode == "sumi" and q_offset:
        nhb = min(nk, -(-min(n_history, sk) // k_chunk)) if n_history else 0
        d0 = min(nk - 1, (q_offset + qi * q_chunk) // k_chunk)
        return list(range(nhb)) + [j for j in range(d0, n_vis) if j >= nhb]
    return list(range(n_vis))


def chunked_attention(q, k, v, mode: str, *, window: int = 0, n_history: int = 0,
                      q_chunk: int = 1024, k_chunk: int = 1024,
                      q_offset: int = 0):
    """Online-softmax attention over KV chunks.

    Shapes as in reference_attention.  KV chunks that a q chunk provably
    cannot see under the static mask are skipped outright, so FLOPs match
    the mask support rather than the dense S^2 rectangle:

      ``sliding``  only the in-window KV slice per q chunk (S*window);
      ``causal``   chunks at-or-below the diagonal (~S^2/2, exact skip);
      ``sumi``     ditto — candidate rows never look above their own
                   position, and the cached-candidate path (``q_offset`` >
                   0) touches history chunks + the self diagonal only;
      ``full``     every chunk (no structure to exploit).

    Skipped chunks are numerically inert in the online softmax (their masked
    scores contribute exact zeros), so outputs are identical to the
    visit-everything formulation.

    ``q_offset`` shifts the query positions against the KV positions — the
    cached-history serving paths run suffix/candidate queries against cached
    K/V rows plus their own, so q row i sits at absolute position
    ``q_offset + i``.  Supported for ``sumi`` (candidate scoring) and
    ``causal`` (incremental history extension).
    """
    if q_offset and mode not in ("sumi", "causal"):
        # the sliding fast path slices KV around un-offset q positions —
        # fail loudly rather than window the wrong region (mirrors the
        # pallas kernel's guard)
        raise NotImplementedError(
            f"q_offset is only supported for mode in ('sumi', 'causal'), "
            f"got {mode!r}")
    b, sq, h, d = q.shape
    sk = k.shape[1]
    hkv = k.shape[2]
    g = h // hkv
    q_chunk = min(q_chunk, sq)
    k_chunk = min(k_chunk, sk)
    nq = -(-sq // q_chunk)
    pad_q = nq * q_chunk - sq
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    scale = 1.0 / np.sqrt(d)

    if mode == "sliding" and window and window < sk:
        return _sliding_chunked(q, k, v, window, q_chunk, sq, pad_q)

    nk = -(-sk // k_chunk)
    pad_k = nk * k_chunk - sk
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    ks = k.reshape(b, nk, k_chunk, hkv, d)
    vs = v.reshape(b, nk, k_chunk, hkv, d)

    def q_block(qi, q_blk, ids, k_sel, v_sel):
        """Online softmax of one q chunk over the selected KV chunks.
        ``qi`` may be a Python int (per-chunk block lists) or traced (the
        uniform-visibility scan path)."""
        q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)
        qf = q_blk.astype(jnp.float32).reshape(b, q_chunk, hkv, g, d) * scale

        def kv_step(carry, inp):
            m, l, acc = carry
            ki, k_blk, v_blk = inp
            k_pos = ki * k_chunk + jnp.arange(k_chunk)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k_blk.astype(jnp.float32))
            msk = mask_value(q_pos[:, None], k_pos[None, :], mode,
                             window=window, n_history=n_history)
            msk = msk & (k_pos[None, :] < sk)
            s = jnp.where(msk[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, v_blk.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, g, q_chunk), -1e30, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, q_chunk, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (ids, k_sel, v_sel),
            unroll=flags.unroll_scans())
        o = acc / jnp.maximum(l[..., None], 1e-30)
        return jnp.moveaxis(o, 3, 1).reshape(b, q_chunk, h, d)  # bhgqd->bqhgd

    if mode in ("causal", "sumi"):
        # python loop over q chunks: the visible-KV count varies per chunk,
        # so each iteration scans its own (static) block list — trace size
        # grows with nq, FLOPs shrink to the mask support
        def one(qi: int):
            ids = jnp.asarray(
                _visible_kv_blocks(mode, qi, q_chunk=q_chunk,
                                   k_chunk=k_chunk, nk=nk, sk=sk,
                                   n_history=n_history, q_offset=q_offset),
                jnp.int32)
            k_sel = jnp.moveaxis(jnp.take(ks, ids, axis=1), 1, 0)
            v_sel = jnp.moveaxis(jnp.take(vs, ids, axis=1), 1, 0)
            return q_block(qi, q[:, qi * q_chunk:(qi + 1) * q_chunk],
                           ids, k_sel, v_sel)
        out = jnp.concatenate([one(qi) for qi in range(nq)], axis=1)
    else:
        # full mode sees every KV chunk from every q chunk: one outer scan
        # keeps trace size O(1) in nq (no per-chunk specialization to gain)
        ids = jnp.arange(nk, dtype=jnp.int32)
        k_all = jnp.moveaxis(ks, 1, 0)
        v_all = jnp.moveaxis(vs, 1, 0)
        q_blocks = jnp.moveaxis(q.reshape(b, nq, q_chunk, h, d), 1, 0)
        _, out = jax.lax.scan(
            lambda _, args: (None, q_block(args[0], args[1],
                                           ids, k_all, v_all)),
            None, (jnp.arange(nq), q_blocks), unroll=flags.unroll_scans())
        out = jnp.moveaxis(out, 0, 1).reshape(b, nq * q_chunk, h, d)
    return out[:, :sq].astype(q.dtype)


def _sliding_chunked(q, k, v, window: int, q_chunk: int, sq: int, pad_q: int):
    """Sliding-window chunked attention: per q chunk slice KV[start:start+W+C].

    Compute is O(S * (W + C)) instead of O(S^2)."""
    b, sq_p, h, d = q.shape
    sk = k.shape[1]
    hkv = k.shape[2]
    g = h // hkv
    nq = sq_p // q_chunk
    span = window + q_chunk  # kv span each q chunk can see
    span = min(span, sk)
    scale = 1.0 / np.sqrt(d)

    def q_block(qi, q_blk):
        q_pos = qi * q_chunk + jnp.arange(q_chunk)
        start = jnp.clip(qi * q_chunk + q_chunk - span, 0, max(sk - span, 0))
        k_blk = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
        v_blk = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
        k_pos = start + jnp.arange(span)
        qf = q_blk.astype(jnp.float32).reshape(b, q_chunk, hkv, g, d) * scale
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k_blk.astype(jnp.float32))
        msk = mask_value(q_pos[:, None], k_pos[None, :], "sliding", window=window)
        msk = msk & (k_pos[None, :] < sk)
        s = jnp.where(msk[None, None, None], s, -1e30)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bhgqd", w, v_blk.astype(jnp.float32))
        return jnp.moveaxis(o, 3, 1).reshape(b, q_chunk, h, d)

    q_blocks = jnp.moveaxis(q.reshape(b, nq, q_chunk, h, d), 1, 0)
    _, out = jax.lax.scan(
        lambda _, args: (None, q_block(*args)), None,
        (jnp.arange(nq), q_blocks), unroll=flags.unroll_scans())
    out = jnp.moveaxis(out, 0, 1).reshape(b, nq * q_chunk, h, d)
    return out[:, :sq].astype(q.dtype)


# ---------------------------------------------------------------------------
# single-token decode attention (memory-bound gather; no kernel needed)
# ---------------------------------------------------------------------------

def decode_attention(q, k_cache, v_cache, cur_len, *, window: int = 0):
    """q [B,1,H,D]; caches [B,Smax,Hkv,D]; cur_len = tokens valid in cache
    (including the new one).  Sliding window masks positions older than W."""
    b, _, h, d = q.shape
    smax = k_cache.shape[1]
    hkv = k_cache.shape[2]
    g = h // hkv
    qf = q.astype(jnp.float32).reshape(b, hkv, g, d)
    s = jnp.einsum("bhgd,bkhd->bhgk", qf, k_cache.astype(jnp.float32)) / np.sqrt(d)
    pos = jnp.arange(smax)[None, :]
    cur = jnp.reshape(jnp.asarray(cur_len), (-1, 1))     # scalar or [B]
    valid = pos < cur
    if window:
        valid = valid & (pos >= cur - window)
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", w, v_cache.astype(jnp.float32))
    return o.reshape(b, 1, h, d).astype(q.dtype)


def _masked_attention_pos(q, k, v, q_pos, k_pos, mode: str, *, window: int):
    """Attention with explicit absolute positions (context-parallel local
    shards).  q [B,Sq,H,D], k/v [B,Sk,Hkv,D]; q_pos [Sq], k_pos [Sk]."""
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qf = q.astype(jnp.float32).reshape(b, sq, hkv, g, d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k.astype(jnp.float32)) / np.sqrt(d)
    msk = mask_value(q_pos[:, None], k_pos[None, :], mode, window=window)
    msk = msk & (k_pos[None, :] >= 0)
    s = jnp.where(msk[None, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    w = jnp.where(msk.any(-1)[None, None, None, :, None], w, 0.0)
    o = jnp.einsum("bhgqk,bkhd->bhgqd", w, v.astype(jnp.float32))
    return jnp.moveaxis(o, 3, 1).reshape(b, sq, h, d).astype(q.dtype)


def context_parallel_attention(q, k, v, mode: str, *, window: int, mesh,
                               seq_axis: str = "model"):
    """Context parallelism over ``seq_axis`` (shard_map, beyond-paper §Perf).

    q/k/v [B,S,H,D] with batch sharded over data/pod and S over ``seq_axis``.
      sliding: halo exchange — each shard ppermutes its last ``window`` K/V
               to the next shard; attention is fully local (exact for SWA).
      causal/full: K/V all-gathered over the seq axis; Q stays local.
    Compute uses all mesh axes; comm is O(window) or O(S*Hkv*D) per layer
    instead of O(S*d_model) activation all-reduces.
    """
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[seq_axis]
    batch_axes = tuple(a for a in mesh.axis_names if a != seq_axis)
    s_total = q.shape[1]
    s_loc = s_total // n

    def local_fn(ql, kl, vl):
        idx = jax.lax.axis_index(seq_axis)
        off = idx * s_loc
        q_pos = off + jnp.arange(s_loc)
        if mode == "sliding" and window and window <= s_loc:
            perm = [(i, (i + 1) % n) for i in range(n)]
            k_halo = jax.lax.ppermute(kl[:, -window:], seq_axis, perm)
            v_halo = jax.lax.ppermute(vl[:, -window:], seq_axis, perm)
            kk = jnp.concatenate([k_halo, kl], axis=1)
            vv = jnp.concatenate([v_halo, vl], axis=1)
            k_pos = off - window + jnp.arange(window + s_loc)
            # shard 0's halo wraps from the last shard -> masked (k_pos < 0)
            return _masked_attention_pos(ql, kk, vv, q_pos, k_pos, "sliding",
                                         window=window)
        kk = jax.lax.all_gather(kl, seq_axis, axis=1, tiled=True)
        vv = jax.lax.all_gather(vl, seq_axis, axis=1, tiled=True)
        k_pos = jnp.arange(s_total)
        return _masked_attention_pos(ql, kk, vv, q_pos, k_pos, mode,
                                     window=window)

    spec = P(batch_axes, seq_axis, None, None)
    return jax.shard_map(local_fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def attention(q, k, v, mode: str, *, impl: str = "chunked", window: int = 0,
              n_history: int = 0, temperature=None, q_offset: int = 0):
    """Dispatch wrapper used by the transformer stack.

    ``impl="fused"`` is the FKE candidate-scoring engine
    (kernels/fused_score): the cached-candidate SUMI case (``q_offset > 0``
    — every query is a candidate against ``n_history`` cached rows plus its
    own key) splits the KV axis at ``n_history`` and runs the two-segment
    fused path without re-materializing the concatenation; other (mode,
    offset) combinations have no fused kernel and fall back to ``chunked``
    (the serving entry points in core/sumi.py call the fused ops directly
    with separate operands, so this route only serves callers that already
    concatenated)."""
    if impl == "fused":
        if mode == "sumi" and q_offset and q_offset == n_history \
                and k.shape[1] == n_history + q.shape[1]:
            from repro.kernels.fused_score import ops as fs_ops
            return fs_ops.fused_cached_attention(
                q, k[:, :n_history], v[:, :n_history],
                k[:, n_history:], v[:, n_history:],
                temperature=temperature)
        impl = "chunked"
    if impl == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops
        return fa_ops.flash_attention(q, k, v, mode, window=window,
                                      n_history=n_history, q_offset=q_offset)
    if impl == "cp":
        from repro import sharding as shd
        active = shd._ACTIVE.get()
        if active is not None and mode in ("sliding", "causal", "full"):
            mesh = active[0]
            if "model" in mesh.axis_names and \
                    q.shape[1] % mesh.shape["model"] == 0:
                return context_parallel_attention(q, k, v, mode,
                                                  window=window, mesh=mesh)
        impl = "chunked"
    if impl == "reference" or q.shape[1] * k.shape[1] <= 256 * 256:
        return reference_attention(q, k, v, mode, window=window,
                                   n_history=n_history, temperature=temperature,
                                   q_offset=q_offset)
    return chunked_attention(q, k, v, mode, window=window, n_history=n_history,
                             q_offset=q_offset)
