"""Pure-jnp fp32 oracle for the fused candidate-scoring kernel (FKE).

The oracle spells out exactly what the fused paths must compute, as the
framework-composed chain the engine ran before the FKE existed:

  1. dequantize the pooled history K/V (same formula as
     ``serving/kv_cache.py::dequantize_leaf`` — a quantized operand is
     ``values * scale / 127`` cast back to the compute dtype);
  2. gather each batch row's KV view through the dedup ``row_index``;
  3. concatenate history and candidate K/V along the position axis;
  4. run materialized-score reference attention (SUMI with
     ``q_offset = n_history`` for cached-candidate scoring, causal with
     ``q_offset = prefix_len`` for incremental suffix extension).

Because steps 1–4 literally reuse the framework reference ops, the oracle
is **bitwise-identical** to ``sumi.cached_candidate_attention`` /
``sumi.extend_attention`` under ``impl="reference"`` on dequantized
operands; the Pallas kernel and the fused jnp fast path are gated against
it at bf16-style tolerances (they reassociate the scale multiply and skip
intermediate roundings).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models import attention as A


def dequantize_values(values, scale, dtype):
    """Invert pool quantization on a raw (values, scale) operand pair.

    Mirrors ``serving/kv_cache.py::dequantize_leaf`` bitwise: ``scale is
    None`` marks a plain cast (bf16 storage or a no-op for native), int8
    values dequantize through the per-(layer, head) absmax scale."""
    if scale is None:
        return jnp.asarray(values).astype(dtype)
    return (jnp.asarray(values, jnp.float32)
            * (jnp.asarray(scale) / 127.0)).astype(dtype)


def _prep(k, v, k_scale, v_scale, row_index, dtype):
    """Steps 1–2: dequantize + gather the per-row KV views."""
    k = dequantize_values(k, k_scale, dtype)
    v = dequantize_values(v, v_scale, dtype)
    if row_index is not None:
        k = jnp.take(k, row_index, axis=0)
        v = jnp.take(v, row_index, axis=0)
    return k, v


def cached_reference(q, k_hist, v_hist, k_cand, v_cand, *,
                     k_scale=None, v_scale=None, row_index=None,
                     kv_dtype=None, temperature=None):
    """Cached-candidate SUMI oracle.  ``q``/``k_cand``/``v_cand``
    [B,M,H(kv),D]; ``k_hist``/``v_hist`` [U,S,Hkv,D] stored values with
    optional [U,1,Hkv,1] scales and a [B] ``row_index`` gather."""
    dtype = kv_dtype or q.dtype
    if temperature is not None:
        q = q / jnp.asarray(temperature, q.dtype)
    kh, vh = _prep(k_hist, v_hist, k_scale, v_scale, row_index, dtype)
    n_history = kh.shape[1]
    k = jnp.concatenate([kh, k_cand.astype(dtype)], axis=1)
    v = jnp.concatenate([vh, v_cand.astype(dtype)], axis=1)
    return A.reference_attention(q, k, v, "sumi", n_history=n_history,
                                 q_offset=n_history)


def decode_reference(q, k_hist, v_hist, k_cand, v_cand, lengths, *,
                     k_scale=None, v_scale=None, row_index=None,
                     kv_dtype=None, temperature=None):
    """Generative-decode oracle: cached-candidate SUMI scoring over a
    PADDED beam-cache operand whose valid prefix per pool row is
    ``lengths[u]`` (<= S).  Dequantize -> gather (1-D per batch row or
    2-D per candidate, lengths riding the same index) -> materialized
    masked softmax: candidate i sees its row's valid history prefix plus
    exactly its own key.  ``lengths == 0`` rows degenerate to a softmax
    over the self key alone."""
    dtype = kv_dtype or q.dtype
    if temperature is not None:
        q = q / jnp.asarray(temperature, q.dtype)
    kh = dequantize_values(k_hist, k_scale, dtype)
    vh = dequantize_values(v_hist, v_scale, dtype)
    lens = jnp.asarray(lengths, jnp.int32)
    if row_index is not None:
        kh = jnp.take(kh, row_index, axis=0)
        vh = jnp.take(vh, row_index, axis=0)
        lens = jnp.take(lens, row_index, axis=0)
    b, m, h, d = q.shape
    hkv = k_cand.shape[2]
    g = h // hkv
    s = kh.shape[-3]
    if kh.ndim == 4:                    # shared per batch row -> [B,M,...]
        kh = jnp.broadcast_to(kh[:, None], (b, m) + kh.shape[1:])
        vh = jnp.broadcast_to(vh[:, None], (b, m) + vh.shape[1:])
    if lens.ndim == 1:
        lens = jnp.broadcast_to(lens[:, None], (b, m))
    qf = q.astype(jnp.float32).reshape(b, m, hkv, g, d) / math.sqrt(d)
    s_hist = jnp.einsum("bmhgd,bmshd->bmhgs", qf, kh.astype(jnp.float32))
    s_self = jnp.einsum("bmhgd,bmhd->bmhg", qf, k_cand.astype(jnp.float32))
    ok = (jnp.arange(s)[None, None, :] < lens[:, :, None])
    s_hist = jnp.where(ok[:, :, None, None, :], s_hist, -1e30)
    logits = jnp.concatenate([s_hist, s_self[..., None]], axis=-1)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bmhgs,bmshd->bmhgd", p[..., :s], vh.astype(jnp.float32))
    o = o + p[..., s][..., None] * v_cand.astype(jnp.float32)[:, :, :, None, :]
    return o.reshape(b, m, h, d).astype(q.dtype)


def extend_reference(q, k_prefix, v_prefix, k_suffix, v_suffix, *,
                     k_scale=None, v_scale=None, row_index=None,
                     kv_dtype=None, temperature=None):
    """Incremental-extension (causal) oracle: suffix queries at absolute
    position ``prefix_len + i`` over ``concat(prefix, suffix)`` KV."""
    dtype = kv_dtype or q.dtype
    if temperature is not None:
        q = q / jnp.asarray(temperature, q.dtype)
    kp, vp = _prep(k_prefix, v_prefix, k_scale, v_scale, row_index, dtype)
    p0 = kp.shape[1]
    k = jnp.concatenate([kp, k_suffix.astype(dtype)], axis=1)
    v = jnp.concatenate([vp, v_suffix.astype(dtype)], axis=1)
    return A.reference_attention(q, k, v, "causal", q_offset=p0)


def pointwise_reference(q, k_hist, v_hist, k_cand, v_cand, bias_hist, *,
                        n_norm: float, mode: str = "cached", self_bias=0.0,
                        bias_cand=None, k_scale=None, v_scale=None,
                        row_index=None):
    """Pointwise (HSTU) oracle: dequantize -> gather (1-D per batch row or
    2-D per candidate) -> one materialized [.., S + M] score row per query
    with its bias and mask -> ``SiLU(s + bias) / n_norm`` weights ->
    ``@ v``.  ``bias_hist`` [U, 1 or M, S]; cached mode sees the history
    and the query's own key (``self_bias``), extend mode the history and
    the causal suffix (``bias_cand`` [B, M, M])."""
    f32 = jnp.float32
    kh = dequantize_values(k_hist, k_scale, f32)
    vh = dequantize_values(v_hist, v_scale, f32)
    bh = jnp.asarray(bias_hist, f32)
    b, m, h, d = q.shape
    hkv = k_cand.shape[2]
    g = h // hkv
    if row_index is not None:
        kh = jnp.take(kh, row_index, axis=0)
        vh = jnp.take(vh, row_index, axis=0)
        bh = jnp.take(bh, row_index, axis=0)
    if kh.ndim == 4:                    # shared per batch row -> [B,M,...]
        kh = jnp.broadcast_to(kh[:, None], (b, m) + kh.shape[1:])
        vh = jnp.broadcast_to(vh[:, None], (b, m) + vh.shape[1:])
        bh = jnp.broadcast_to(bh, (b, m, bh.shape[-1]))
    else:
        bh = bh[:, :, 0]
    s = kh.shape[2]
    qf = q.astype(f32).reshape(b, m, hkv, g, d)
    kc = k_cand.astype(f32)
    vc = v_cand.astype(f32)
    s_hist = jnp.einsum("bmhgd,bmshd->bmhgs", qf, kh) \
        + bh[:, :, None, None, :]
    if mode == "cached":
        s_self = jnp.einsum("bmhgd,bmhd->bmhg", qf, kc) + self_bias
        w_self = jax.nn.silu(s_self) / n_norm
        o = jnp.einsum("bmhgs,bmshd->bmhgd", jax.nn.silu(s_hist) / n_norm,
                       vh)
        o = o + w_self[..., None] * vc[:, :, :, None, :]
    else:
        s_suf = jnp.einsum("bmhgd,bshd->bmhgs", qf, kc) \
            + jnp.asarray(bias_cand, f32)[:, :, None, None, :]
        causal = jnp.arange(m)[None, :] <= jnp.arange(m)[:, None]
        w_suf = jnp.where(causal[None, :, None, None, :],
                          jax.nn.silu(s_suf), 0.0) / n_norm
        o = jnp.einsum("bmhgs,bmshd->bmhgd", jax.nn.silu(s_hist) / n_norm,
                       vh)
        o = o + jnp.einsum("bmhgs,bshd->bmhgd", w_suf, vc)
    return o.reshape(b, m, h, d).astype(q.dtype)
