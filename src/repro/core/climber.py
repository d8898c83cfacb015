"""Climber — the GR model FLAME serves (paper §2.1, Fig 2).

Architecture (faithful to the paper's description):
  * the user behavior sequence is reorganized into ``N_b`` sub-sequences,
    each processed by an independent transformer block (``layers_per_block``
    layers) — attention complexity drops from O(n^2 d) to O(n^2 d / N_b);
  * an adaptive temperature coefficient is applied before softmax in every
    attention (learned per block+layer, softplus-positive);
  * the M candidate items are concatenated after each block's sub-sequence
    and scored in parallel under the SUMI mask;
  * per-candidate block outputs are fused with bit-wise (per-dimension)
    gating across blocks;
  * a multi-task expert head (MMoE-style) produces ``num_tasks`` scores.

Training objective: multi-task binary cross-entropy against per-candidate
labels (click/like/finish-style engagement tasks).
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro import sharding as shd
from repro.core import sumi
from repro.models import attention as A
from repro.models import layers as L
from repro.models.ffn import ffn_init, ffn_apply
from repro.models.model import HostPlane, ModelBundle
from repro.types import ModelConfig, ShapeConfig

N_SIDE_FEATURES = 12   # "a dozen pieces of side information" (paper §4.1)


def _block_init(key, cfg, n_layers: int):
    """One transformer block's stacked params (+ adaptive temperature)."""
    ks = jax.random.split(key, 2)
    return {
        "norm1": L.norm_init(cfg, cfg.d_model, stacked=n_layers),
        "attn": A.qkv_init(ks[0], cfg, stacked=n_layers),
        "norm2": L.norm_init(cfg, cfg.d_model, stacked=n_layers),
        "ffn": ffn_init(ks[1], cfg, stacked=n_layers),
        # adaptive temperature, one per layer: tau = softplus(t) + 0.5
        "temp": L.zeros_init((1,), (None,), stacked=n_layers, fill=0.55,
                             dtype=jnp.float32),
    }


def climber_init(key, cfg: ModelConfig):
    c = cfg.climber
    d = cfg.d_model
    ks = jax.random.split(key, 8)
    blocks = {f"b{i}": _block_init(jax.random.fold_in(ks[0], i), cfg,
                                   c.layers_per_block)
              for i in range(c.num_blocks)}
    dh = d  # expert hidden dim
    params = {
        "embed": {"embedding": L.dense_init(
            ks[1], (cfg.vocab_size, d), ("vocab", "embed"), scale=0.02)},
        "pos_embed": L.dense_init(ks[2], (8192, d), (None, "embed"), scale=0.02),
        "side_proj": L.dense_init(ks[3], (N_SIDE_FEATURES, d), (None, "embed")),
        "blocks": blocks,
        # bit-wise gating fusion across blocks
        "gate_w": L.dense_init(ks[4], (c.num_blocks, d), (None, "embed"),
                               scale=0.02),
        "gate_b": L.zeros_init((c.num_blocks, d), (None, "embed")),
        "out_norm": L.norm_init(cfg, d),
        # MMoE head: experts, per-task gates, per-task towers
        "experts_w1": L.dense_init(ks[5], (c.num_experts_head, d, dh),
                                   (None, "embed", "mlp"), fan_in_axes=(1,)),
        "experts_w2": L.dense_init(ks[6], (c.num_experts_head, dh, dh),
                                   (None, "mlp", "embed"), fan_in_axes=(1,)),
        "task_gates": L.dense_init(ks[7], (c.num_tasks, d, c.num_experts_head),
                                   (None, "embed", None), fan_in_axes=(1,)),
        "task_towers": L.dense_init(jax.random.fold_in(key, 9),
                                    (c.num_tasks, dh), (None, "embed"),
                                    fan_in_axes=(1,)),
    }
    return L.split_params(params)


def _tau(p):
    """Adaptive temperature for one layer's params."""
    return jax.nn.softplus(p["temp"][0]) + 0.5


def _history_block_inputs(params, batch: Dict, cfg) -> list:
    """Embed the history and reorganize it into per-block input sequences:
    [sub-sequence + positional embeddings, context side token].

    The side token rides at the END of each block's history prefix (not the
    front): under the causal prefix mask the history items then never attend
    to it, so their per-layer K/V depend *only* on the item ids — the
    property PDA v2's incremental extension exploits (a side-feature-only
    change re-encodes one token per block instead of the whole prefix).
    The side token itself still sees every history item, and candidates see
    history + side + self, so side information reaches every score."""
    hist = jnp.take(params["embed"]["embedding"], batch["history"], axis=0)
    b, n, d = hist.shape
    side = jnp.einsum("bf,fd->bd", batch["side"].astype(hist.dtype),
                      params["side_proj"])[:, None]
    nb = cfg.climber.num_blocks
    sub = hist.reshape(b, nb, n // nb, d)
    return [jnp.concatenate([sub[:, i] + params["pos_embed"][None, :n // nb],
                             side], axis=1)
            for i in range(nb)]


def _fuse_and_head(params, h, cfg):
    """Per-candidate block outputs h [B,M,Nb,d] -> task logits [B,M,T]."""
    # bit-wise gating fusion: per-dimension softmax over blocks
    gate_logits = h.astype(jnp.float32) * params["gate_w"].astype(jnp.float32) \
        + params["gate_b"].astype(jnp.float32)
    gates = jax.nn.softmax(gate_logits, axis=2)
    fused = (gates * h.astype(jnp.float32)).sum(axis=2)  # [B,M,d]
    fused = shd.constrain_ctx(fused, "batch", None, None)
    fused = L.apply_norm(cfg, params["out_norm"], fused)

    # MMoE expert head
    e1 = jnp.einsum("bmd,edh->bmeh", fused, params["experts_w1"].astype(jnp.float32))
    e1 = jax.nn.gelu(e1)
    e2 = jnp.einsum("bmeh,ehg->bmeg", e1, params["experts_w2"].astype(jnp.float32))
    tg = jax.nn.softmax(jnp.einsum("bmd,tde->bmte", fused,
                                   params["task_gates"].astype(jnp.float32)),
                        axis=-1)
    mix = jnp.einsum("bmte,bmeg->bmtg", tg, e2)
    logits = jnp.einsum("bmtg,tg->bmt", mix, params["task_towers"].astype(jnp.float32))
    return logits


def _layer_tail(p, x, o, cfg, impl: str):
    """Everything after attention in one layer step: out-projection +
    residual + norm + FFN + residual.  ``impl="fused"`` routes the FKE
    epilogue (reuses the ``kernels/fused_ffn`` Pallas kernel on TPU; the
    identical framework composition elsewhere)."""
    if impl == "fused":
        from repro.kernels.fused_score import ops as fs_ops
        return fs_ops.block_epilogue(x, o, p["attn"], p["norm2"],
                                     p["ffn"], cfg)
    x = x + A.project_out(p["attn"], o)
    h2 = L.apply_norm(cfg, p["norm2"], x)
    return x + ffn_apply(p["ffn"], h2, cfg, impl=impl)


def _block_forward(bp, x, n_history: int, cfg, impl: str):
    """x [B,S,d] through one stacked transformer block under the SUMI mask.

    All candidates share position ``n_history`` (each is a hypothetical
    "next item"), which makes scoring permutation-invariant across the
    candidate set — required for DSO chunk-splitting to be exact."""
    b, s, d = x.shape
    pos = jnp.concatenate([jnp.arange(n_history),
                           jnp.full((s - n_history,), n_history)])
    positions = jnp.broadcast_to(pos, (b, s))

    def layer(x, p):
        h = L.apply_norm(cfg, p["norm1"], x)
        q, k, v = A.project_qkv(p["attn"], h, cfg, positions)
        # mesh-sharded serving: batch over data, heads tensor-parallel
        # (no-op without an active mesh_rules context)
        q = shd.constrain_ctx(q, "batch", None, "heads", None)
        o = sumi.sumi_attention(q, k, v, n_history, impl=impl,
                                temperature=_tau(p))
        return _layer_tail(p, x, o, cfg, impl), None

    from repro.models.transformer import scan_or_unroll
    x, _ = scan_or_unroll(layer, x, bp)
    return x


def _block_encode_kv(bp, x, cfg, impl: str):
    """History-only causal pass over one block; returns per-layer K/V.

    Under the SUMI mask the history prefix is self-contained (causal among
    itself, blind to candidates), so the K/V recorded here are exactly the
    history rows the monolithic pass would compute."""
    b, s, d = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    def layer(x, p):
        h = L.apply_norm(cfg, p["norm1"], x)
        q, k, v = A.project_qkv(p["attn"], h, cfg, positions)
        q = shd.constrain_ctx(q, "batch", None, "heads", None)
        # n_history == s: the SUMI mask degenerates to causal here
        o = sumi.sumi_attention(q, k, v, s, impl=impl, temperature=_tau(p))
        return _layer_tail(p, x, o, cfg, impl), (k, v)

    from repro.models.transformer import scan_or_unroll
    _, kv = scan_or_unroll(layer, x, bp)
    return kv                                  # (k, v), each [L,B,s,Hkv,D]


def _block_score(bp, cand, k_hist, v_hist, cfg, impl: str, *,
                 k_scale=None, v_scale=None, row_index=None):
    """Candidate-only pass for one block against cached history K/V.

    ``cand`` [B,M,d]; ``k_hist``/``v_hist`` [L,U,n_hist,Hkv,D].  Candidates
    all sit at RoPE position ``n_hist`` exactly as in the monolithic pass.

    FKE operands: the history K/V may arrive in the pool's stored
    precision with per-(layer, row, head) ``k_scale``/``v_scale``
    ([L,U,1,Hkv,1]) and a ``row_index`` [B] mapping batch rows onto the
    ``U`` unique pool rows (KV-row dedup) — or [B, M] mapping every
    CANDIDATE onto its own pool row (DSO v2 segment packing: one row may
    carry segments of several users).  ``impl="fused"`` consumes them
    in-kernel; other impls materialize the dequantized gather first (see
    ``sumi.cached_candidate_attention``)."""
    b, m, d = cand.shape
    n_hist = k_hist.shape[2]
    positions = jnp.broadcast_to(jnp.asarray(n_hist), (b, m))
    has_scale = k_scale is not None

    def layer(x, inp):
        if has_scale:
            p, kh, vh, khs, vhs = inp
        else:
            (p, kh, vh), khs, vhs = inp, None, None
        h = L.apply_norm(cfg, p["norm1"], x)
        q, k, v = A.project_qkv(p["attn"], h, cfg, positions)
        # mesh-sharded serving: candidate queries shard batch-over-data and
        # heads-over-model; the stacked pool rows kh/vh [U,S,Hkv,D] keep
        # their user axis replicated (so the per-candidate row gather never
        # crosses shards) with heads — or, CP fallback, the history
        # length — on the model axis
        q = shd.constrain_ctx(q, "batch", None, "heads", None)
        kh = shd.constrain_ctx(kh, None, "cache_seq_shard", "cache_heads",
                               None)
        vh = shd.constrain_ctx(vh, None, "cache_seq_shard", "cache_heads",
                               None)
        o = sumi.cached_candidate_attention(
            q, kh, vh, k, v, impl=impl, temperature=_tau(p),
            k_scale=khs, v_scale=vhs, row_index=row_index)
        return _layer_tail(p, x, o, cfg, impl), None

    from repro.models.transformer import scan_or_unroll
    inp = (bp, k_hist, v_hist)
    if has_scale:
        inp = inp + (k_scale, v_scale)
    x, _ = scan_or_unroll(layer, cand, inp)
    return x


def encode_history(params, batch: Dict, cfg: ModelConfig, *,
                   impl: str = "reference"):
    """batch: history [B,n] ids, side [B,F] -> HistoryKV pytree.

    Per block ``b{i}``: {"k", "v"} with shape [B, L, n_hist_block, Hkv, D]
    (batch axis leading, so serving can stack pool entries from different
    requests along axis 0).  n_hist_block = n // num_blocks + 1: positions
    ``0..w-1`` are the block's history items (K/V depending only on the
    item ids) and position ``w`` is the context side token folding the side
    features in (see :func:`_history_block_inputs`); ``w = n //
    num_blocks``.  :func:`extend_history` can therefore refresh a cached
    entry by re-encoding only the suffix that actually changed."""
    kv = {}
    for i, xb in enumerate(_history_block_inputs(params, batch, cfg)):
        k, v = _block_encode_kv(params["blocks"][f"b{i}"], xb, cfg, impl)
        kv[f"b{i}"] = {"k": jnp.moveaxis(k, 1, 0), "v": jnp.moveaxis(v, 1, 0)}
    return kv


def _block_extend_kv(bp, x_suf, k_pref, v_pref, cfg, impl: str):
    """Suffix-only causal pass for one block against cached prefix K/V.

    ``x_suf`` [B,S_suf,d] holds the block inputs from position ``P``
    onward (changed history items + the side token); ``k_pref``/``v_pref``
    [L,B,P,Hkv,D] are the trusted rows of a cached encode.  Returns the
    per-layer K/V of the suffix positions — bitwise what a full
    :func:`_block_encode_kv` would produce for those rows (reference
    impl), because causal attention at position >= P sees exactly
    ``concat(prefix, suffix)``."""
    b, s_suf, d = x_suf.shape
    p0 = k_pref.shape[2]
    positions = jnp.broadcast_to(p0 + jnp.arange(s_suf), (b, s_suf))

    def layer(x, inp):
        p, kh, vh = inp
        h = L.apply_norm(cfg, p["norm1"], x)
        q, k, v = A.project_qkv(p["attn"], h, cfg, positions)
        o = sumi.extend_attention(q, kh, vh, k, v, impl=impl,
                                  temperature=_tau(p))
        return _layer_tail(p, x, o, cfg, impl), (k, v)

    from repro.models.transformer import scan_or_unroll
    _, kv = scan_or_unroll(layer, x_suf, (bp, k_pref, v_pref))
    return kv                                  # (k, v), each [L,B,S_suf,Hkv,D]


def _dequant_stored_entry(entry, dtype):
    """A pool entry leaf is either a plain array or a raw ``(values,
    scale)`` view in the pool's stored precision (``scale is None`` marks
    a plain bf16 cast — see ``serving/kv_cache.py::raw_kv_view``).
    Dequantize IN-GRAPH to the compute dtype: the fused extend executors
    are compiled against raw pool specs, so the stale entry ships to the
    device in its stored (int8: 4x smaller) representation and this is
    the only dequantization it ever sees — same formula as the pool's
    host-side ``dequantize_leaf``, so the result is bitwise identical."""
    if isinstance(entry, tuple):
        from repro.kernels.fused_score.ref import dequantize_values
        values, scale = entry
        return dequantize_values(values, scale, dtype)
    return entry


def extend_history(params, history_kv, batch: Dict, cfg: ModelConfig, *,
                   prefix_len: int, impl: str = "reference"):
    """Incremental suffix extension of a cached HistoryKV (PDA v2).

    Trusts the first ``prefix_len`` positions of the model's history window
    to be unchanged since ``history_kv`` was encoded, and re-encodes only
    the remainder: per block, the history items at window positions >=
    ``prefix_len`` plus the side token (which always re-encodes — side
    features average the *full* upstream history, so any history change
    moves them).  ``prefix_len == n`` is the dominant serving case: a
    tail-append beyond the model window re-encodes exactly one token per
    block instead of ``n/N_b + 1``.

    Returns a full HistoryKV pytree (cached prefix rows + fresh suffix
    rows), bitwise-identical to ``encode_history(params, batch)`` under the
    reference/chunked impls whenever the trust assumption holds.

    ``history_kv`` leaves may be raw ``(values, scale)`` pool views in the
    pool's stored precision — the quantized-extend-basis path: the stale
    entry is dequantized here, inside the compiled executor, instead of on
    the host before dispatch."""
    n = batch["history"].shape[1]
    nb = cfg.climber.num_blocks
    w = n // nb
    if not 0 <= prefix_len <= n:
        raise ValueError(f"prefix_len must be in [0, {n}], got {prefix_len}")
    kv = {}
    for i, xb in enumerate(_history_block_inputs(params, batch, cfg)):
        p_i = min(max(prefix_len - i * w, 0), w)
        old = history_kv[f"b{i}"]
        k_all = jnp.moveaxis(_dequant_stored_entry(old["k"], xb.dtype), 1, 0)
        v_all = jnp.moveaxis(_dequant_stored_entry(old["v"], xb.dtype), 1, 0)
        k_new, v_new = _block_extend_kv(
            params["blocks"][f"b{i}"], xb[:, p_i:],
            k_all[:, :, :p_i], v_all[:, :, :p_i], cfg, impl)
        k_full = jnp.concatenate([k_all[:, :, :p_i], k_new], axis=2)
        v_full = jnp.concatenate([v_all[:, :, :p_i], v_new], axis=2)
        kv[f"b{i}"] = {"k": jnp.moveaxis(k_full, 1, 0),
                       "v": jnp.moveaxis(v_full, 1, 0)}
    return kv


def _split_stored(entry):
    """A HistoryKV leaf is either a plain [B,L,S,Hkv,D] array or a
    ``(values, scale)`` raw pool view (``serving/kv_cache.py::
    raw_kv_view``); returns (values, scale-or-None) in [L,B,...] layout."""
    values, scale = entry if isinstance(entry, tuple) else (entry, None)
    values = jnp.moveaxis(values, 1, 0)
    if scale is not None:
        scale = jnp.moveaxis(scale, 1, 0)
    return values, scale


def score_candidates(params, history_kv, candidates, cfg: ModelConfig, *,
                     impl: str = "reference", row_index=None):
    """Candidate-only forward against cached history K/V.

    ``candidates`` [B,M] ids; ``history_kv`` from :func:`encode_history` —
    either dequantized arrays or raw pool views (``(values, scale)``
    tuples in the pool's stored precision), with an optional ``row_index``
    [B] mapping batch rows onto unique pool rows (KV-row dedup).  Returns
    task logits [B,M,T] — numerically identical to the candidate slice of
    :func:`climber_forward` (bitwise under the reference impl on
    dequantized operands)."""
    cand = jnp.take(params["embed"]["embedding"], candidates, axis=0)
    if row_index is not None:
        row_index = jnp.asarray(row_index, jnp.int32)
    block_outs = []
    for i in range(cfg.climber.num_blocks):
        kv = history_kv[f"b{i}"]
        kh, khs = _split_stored(kv["k"])
        vh, vhs = _split_stored(kv["v"])
        block_outs.append(_block_score(
            params["blocks"][f"b{i}"], cand, kh, vh, cfg, impl,
            k_scale=khs, v_scale=vhs, row_index=row_index))
    h = jnp.stack(block_outs, axis=2)                   # [B,M,Nb,d]
    return _fuse_and_head(params, h, cfg)


def _block_decode(bp, cand, k_hist, v_hist, lengths, cfg, impl: str, *,
                  k_scale=None, v_scale=None, row_index=None,
                  collect_kv: bool = False):
    """Generative-decode pass for one block against a PADDED beam cache.

    Like :func:`_block_score` but the cached history is a growing beam
    cache whose valid prefix per row is ``lengths`` [B] (or [U] with a
    packed ``row_index`` [B,M] steering every candidate to its own beam
    row).  Each candidate sits at RoPE position ``lengths`` — the next
    slot of ITS OWN sequence — so a decode step over the vocab is
    `score_candidates(M=V)` at the beam's current length.  With
    ``collect_kv`` the per-layer candidate K/V are returned too (the
    append path: the chosen token's K/V are exactly what this pass
    computed for it)."""
    b, m, d = cand.shape
    lengths = jnp.asarray(lengths, jnp.int32)
    if row_index is not None:
        positions = jnp.take(lengths, row_index)
    else:
        positions = jnp.broadcast_to(lengths[:, None], (b, m))
    has_scale = k_scale is not None

    def layer(x, inp):
        if has_scale:
            p, kh, vh, khs, vhs = inp
        else:
            (p, kh, vh), khs, vhs = inp, None, None
        h = L.apply_norm(cfg, p["norm1"], x)
        q, k, v = A.project_qkv(p["attn"], h, cfg, positions)
        q = shd.constrain_ctx(q, "batch", None, "heads", None)
        o = sumi.decode_candidate_attention(
            q, kh, vh, k, v, lengths, impl=impl, temperature=_tau(p),
            k_scale=khs, v_scale=vhs, row_index=row_index)
        return _layer_tail(p, x, o, cfg, impl), \
            ((k, v) if collect_kv else None)

    from repro.models.transformer import scan_or_unroll
    inp = (bp, k_hist, v_hist)
    if has_scale:
        inp = inp + (k_scale, v_scale)
    x, kv = scan_or_unroll(layer, cand, inp)
    return x, kv


def decode_logits(params, history_kv, candidates, lengths, cfg: ModelConfig,
                  *, impl: str = "reference", row_index=None):
    """One generative-decode scoring step: task logits [B,M,T] for M
    next-token candidates against padded beam caches.

    ``history_kv`` leaves are [B,L,S_pad,Hkv,D] (or [U,...] packed) with
    valid prefix ``lengths`` per row; every candidate scores as the
    hypothetical next item of its beam.  At ``lengths == S_pad`` (no
    padding) this is bitwise :func:`score_candidates` — one decode step
    IS `score_candidates(M=V)` + argmax, the oracle identity the decode
    test suite pins down."""
    cand = jnp.take(params["embed"]["embedding"], candidates, axis=0)
    if row_index is not None:
        row_index = jnp.asarray(row_index, jnp.int32)
    block_outs = []
    for i in range(cfg.climber.num_blocks):
        kv = history_kv[f"b{i}"]
        kh, khs = _split_stored(kv["k"])
        vh, vhs = _split_stored(kv["v"])
        x, _ = _block_decode(
            params["blocks"][f"b{i}"], cand, kh, vh, lengths, cfg, impl,
            k_scale=khs, v_scale=vhs, row_index=row_index)
        block_outs.append(x)
    h = jnp.stack(block_outs, axis=2)                   # [B,M,Nb,d]
    return _fuse_and_head(params, h, cfg)


def append_token(params, history_kv, tokens, lengths, cfg: ModelConfig, *,
                 impl: str = "reference"):
    """Write one chosen token's per-layer K/V into every block's padded
    beam cache at position ``lengths`` (the beam's next free slot).

    ``tokens`` [B,1] ids; ``history_kv`` leaves are [B,L,S_pad,Hkv,D] —
    plain (dequantized) arrays under the chunked engine, or raw
    ``(values, scale)`` pool views under ``impl="fused"`` (FKE v2: the
    appended token is quantized IN-GRAPH against the entry's fixed
    per-(row, layer, head) scale and scattered straight into the stored
    int8 values, so the beam cache never leaves the pool's stored
    precision).  ``lengths < S_pad`` is the caller's contract (the engine
    pads caches by the generation budget up front; `dynamic_update_slice`
    clamps, so an unpadded full cache would silently overwrite its last
    history row).  The written K/V are computed by the same decode-pass
    layer chain that scored the token, so an incrementally-grown cache is
    bitwise the cache a monolithic re-encode of history+tokens would
    produce (reference impl) — asserted in tests/test_decode_serving.py."""
    tok = jnp.take(params["embed"]["embedding"], tokens, axis=0)  # [B,1,d]
    lengths = jnp.asarray(lengths, jnp.int32)
    new_kv = {}
    for i in range(cfg.climber.num_blocks):
        kv = history_kv[f"b{i}"]
        kh, khs = _split_stored(kv["k"])
        vh, vhs = _split_stored(kv["v"])
        _, (k_new, v_new) = _block_decode(
            params["blocks"][f"b{i}"], tok, kh, vh, lengths, cfg, impl,
            k_scale=khs, v_scale=vhs, collect_kv=True)

        def scatter(entry, new):
            values, scale = entry if isinstance(entry, tuple) \
                else (entry, None)
            new = jnp.moveaxis(new, 1, 0)               # [B,L,1,Hkv,D]
            if scale is not None:
                # quantize against the entry's FIXED absmax scale
                # ([B,L,1,Hkv,1]) — the stored rows keep their original
                # codes, so only the appended slot rounds (and clips, if
                # the token's K/V exceed the row's absmax)
                new = jnp.clip(
                    jnp.round(new.astype(jnp.float32) / scale * 127.0),
                    -127, 127)
            out = jax.vmap(
                lambda c, t, n: jax.lax.dynamic_update_slice(
                    c, t.astype(c.dtype), (0, n, 0, 0)))(
                values, new, lengths)
            return out if not isinstance(entry, tuple) else (out, scale)
        new_kv[f"b{i}"] = {"k": scatter(kv["k"], k_new),
                           "v": scatter(kv["v"], v_new)}
    return new_kv


def history_kv_specs(params, cfg: ModelConfig, n_history: int,
                     batch: int = 1):
    """ShapeDtypeStruct pytree of the HistoryKV for AOT executor builds."""
    batch_spec = {
        "history": jax.ShapeDtypeStruct((batch, n_history), jnp.int32),
        "side": jax.ShapeDtypeStruct((batch, N_SIDE_FEATURES), jnp.float32),
    }
    return jax.eval_shape(lambda p, b: encode_history(p, b, cfg),
                          params, batch_spec)


def climber_forward(params, batch: Dict, cfg: ModelConfig, *,
                    impl: str = "reference"):
    """batch: history [B,n] ids, candidates [B,M] ids, side [B,F].
    Returns task logits [B, M, num_tasks]."""
    cand = jnp.take(params["embed"]["embedding"], batch["candidates"], axis=0)
    block_outs = []
    for i, xb in enumerate(_history_block_inputs(params, batch, cfg)):
        seq, n_hist = sumi.assemble(xb, cand)
        out = _block_forward(params["blocks"][f"b{i}"], seq, n_hist, cfg, impl)
        block_outs.append(sumi.split_candidates(out, n_hist))
    h = jnp.stack(block_outs, axis=2)                   # [B,M,Nb,d]
    return _fuse_and_head(params, h, cfg)


def build_climber(cfg: ModelConfig) -> ModelBundle:
    c = cfg.climber

    def init(key):
        return climber_init(key, cfg)

    def loss_fn(params, batch, impl: str = "reference"):
        logits = climber_forward(params, batch, cfg, impl=impl)
        labels = batch["labels"].astype(jnp.float32)
        ls = jnp.mean(
            jnp.maximum(logits, 0) - logits * labels
            + jnp.log1p(jnp.exp(-jnp.abs(logits))))
        return ls, {"bce_loss": ls}

    def prefill(params, batch, impl: str = "reference", caches=None):
        """Serving entry: per-candidate multi-task probabilities [B,M,T]."""
        return jax.nn.sigmoid(climber_forward(params, batch, cfg, impl=impl))

    def encode_history_fn(params, batch, impl: str = "reference"):
        """Serving entry: history-only pass -> cacheable HistoryKV pytree."""
        return encode_history(params, batch, cfg, impl=impl)

    def score_candidates_fn(params, history_kv, candidates,
                            impl: str = "reference", row_index=None):
        """Serving entry: candidate-only probabilities [B,M,T] against a
        cached HistoryKV — prefill == score_candidates(encode_history).
        ``history_kv`` may be a raw pool view (stored-precision values +
        scales) and ``row_index`` a [B] KV-row dedup gather; see
        :func:`score_candidates`."""
        return jax.nn.sigmoid(
            score_candidates(params, history_kv, candidates, cfg, impl=impl,
                             row_index=row_index))

    def extend_history_fn(params, history_kv, batch, *, prefix_len: int,
                          impl: str = "reference"):
        """Serving entry: suffix-only re-encode of a cached HistoryKV whose
        first ``prefix_len`` window positions are unchanged."""
        return extend_history(params, history_kv, batch, cfg,
                              prefix_len=prefix_len, impl=impl)

    def history_kv_specs_fn(params, n_history: int, batch: int = 1):
        return history_kv_specs(params, cfg, n_history, batch)

    def decode_logits_fn(params, history_kv, candidates, lengths,
                         impl: str = "reference", row_index=None):
        """Serving entry: one generative-decode step -> per-candidate
        probabilities [B,M,T] (same sigmoid as score_candidates_fn, so a
        decode step at full length is bitwise a score_candidates call)."""
        return jax.nn.sigmoid(
            decode_logits(params, history_kv, candidates, lengths, cfg,
                          impl=impl, row_index=row_index))

    def append_token_fn(params, history_kv, tokens, lengths,
                        impl: str = "reference"):
        """Serving entry: grow every block's padded beam cache by the
        chosen token's K/V at position ``lengths``."""
        return append_token(params, history_kv, tokens, lengths, cfg,
                            impl=impl)

    def decode_step(params, caches, batch, impl: str = "reference"):
        raise NotImplementedError(
            "Climber scores all candidates in one SUMI pass; no decode step.")

    def cache_init(batch, max_len, dtype=jnp.bfloat16):
        raise NotImplementedError("Climber serving is single-pass (no KV cache).")

    def input_specs(shape: ShapeConfig):
        b = shape.global_batch
        n, m = shape.seq_len, shape.n_candidates
        specs = {
            "history": jax.ShapeDtypeStruct((b, n), jnp.int32),
            "candidates": jax.ShapeDtypeStruct((b, m), jnp.int32),
            "side": jax.ShapeDtypeStruct((b, N_SIDE_FEATURES), jnp.float32),
        }
        if shape.kind == "train":
            specs["labels"] = jax.ShapeDtypeStruct((b, m, c.num_tasks),
                                                   jnp.float32)
        return specs

    def input_logical(shape: ShapeConfig):
        lg = {"history": ("batch", None), "candidates": ("batch", None),
              "side": ("batch", None)}
        if shape.kind == "train":
            lg["labels"] = ("batch", None, None)
        return lg

    return ModelBundle(cfg, init, loss_fn, prefill, decode_step,
                       input_specs, input_logical, cache_init,
                       encode_history=encode_history_fn,
                       score_candidates=score_candidates_fn,
                       history_kv_specs=history_kv_specs_fn,
                       extend_history=extend_history_fn,
                       decode_logits=decode_logits_fn,
                       append_token=append_token_fn,
                       host_planes=(HostPlane("history", "window"),
                                    HostPlane("side", "side_features",
                                              N_SIDE_FEATURES, "float32")))
