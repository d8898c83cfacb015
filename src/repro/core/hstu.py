"""HSTU — Hierarchical Sequential Transduction Units (Zhai et al., "Actions
Speak Louder than Words", ICML 2024, arXiv:2402.17152; the authors' code,
github.com/facebookresearch/generative-recommenders), served through the
split serving surface.

One layer, on input ``X`` of width ``d`` (``h`` heads of ``head_dim``):

  * ``U, V, Q, K = split(SiLU(f1(LayerNorm(X))))``, one ``d -> 4 h
    head_dim`` projection with bias;
  * ``A_ij = SiLU(Q_i . K_j + rab_ij) / N`` under the mask, no softmax,
    ``rab_ij = w_pos[i - j] + w_time[bucket(t_i - t_j)]`` (per layer,
    shared over heads), ``N`` the configured sequence length;
  * ``Y = X + f2(LayerNorm(A V) * U)``, ``f2`` ``h head_dim -> d`` with
    bias; no FFN.

``bucket(x) = min(floor(ln(max(|x|, 1)) / 0.301), num_time_buckets)``.
The LayerNorms carry no learned scale or bias (the authors' functional
``layer_norm``).  Input: ``sqrt(d) * embedding + learned absolute
position``.  History query time is the action's own time (the authors'
code uses the next action's, which would make the last cached token stale
on every request).

Candidates are scored under M-FALCON: each sits at position ``n`` after
the ``n`` history actions at the request time (``pda.REQUEST_TIME``) and
attends to the whole history and to itself, never to another candidate —
SUMI's mask.  A ranking head (affine LayerNorm, ``d -> num_tasks``,
sigmoid) turns a candidate's last-layer output into task scores.

The HistoryKV of a row is ``{"k", "v"}`` [B, L, n, H, D] per layer and
``"t"`` [B, n] int32, the action times the history was encoded with: the
pool stores K/V in its precision and the times as they are, and cached
scoring reads the time-bucket bias from them.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pda import REQUEST_TIME
from repro.models import layers as L
from repro.models.model import HostPlane, ModelBundle
from repro.models.transformer import scan_or_unroll
from repro.types import ModelConfig, ShapeConfig

LN_EPS = 1e-6
#: query rows per block of the encode/extend attention: one layer's
#: [h, n, n] scores are never all live at once
QUERY_BLOCK = 512
#: history positions per step of the cached-scoring kernel: packed rows
#: run q blocks of 8 candidates, each streaming its pool row's history, so
#: long steps keep the grid short (4 heads x 64 q blocks x 9 steps a
#: 512-slot row at a 4,096-action window)
HISTORY_BLOCK = 512


def hstu_init(key, cfg: ModelConfig):
    c = cfg.hstu
    d, h, hd, nl = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.n_layers
    ks = jax.random.split(key, 7)
    params = {
        "embed": {"embedding": L.dense_init(
            ks[0], (cfg.vocab_size, d), ("vocab", "embed"), scale=0.02)},
        "pos_embed": L.dense_init(ks[1], (c.max_seq_len + 1, d),
                                  (None, "embed"), scale=0.02),
        "layers": {
            "uvqk": L.dense_init(ks[2], (d, 4 * h * hd), ("embed", "mlp"),
                                 stacked=nl),
            "uvqk_b": L.zeros_init((4 * h * hd,), ("mlp",), stacked=nl),
            "out": L.dense_init(ks[3], (h * hd, d), ("mlp", "embed"),
                                stacked=nl),
            "out_b": L.zeros_init((d,), ("embed",), stacked=nl),
            # relative bias tables: offsets 0..N, buckets 0..num_buckets
            "pos_bias": L.dense_init(ks[4], (c.max_seq_len + 1,), (None,),
                                     scale=0.02, stacked=nl),
            "time_bias": L.dense_init(ks[5], (c.num_time_buckets + 1,),
                                      (None,), scale=0.02, stacked=nl),
        },
        "out_norm": L.norm_init(cfg, d),
        "head_w": L.dense_init(ks[6], (d, c.num_tasks), ("embed", None)),
        "head_b": L.zeros_init((c.num_tasks,), (None,)),
    }
    return L.split_params(params)


def _norm(x):
    """LayerNorm without scale or bias, in f32, cast back."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + LN_EPS)).astype(x.dtype)


def _embed(params, ids, start: int, stop: int, cfg):
    """sqrt(d) * embedding of ``ids`` [B,S] plus the learned positions
    ``start..stop-1`` (one shared position where ``stop == start + 1``):
    a slice of the table, no gather."""
    e = jnp.take(params["embed"]["embedding"], ids, axis=0)
    return e * float(np.sqrt(cfg.d_model)) + params["pos_embed"][start:stop]


def _project(p, x, cfg):
    """U [B,S,h*D] and V, Q, K [B,S,h,D]: SiLU(f1(LayerNorm(x)))."""
    b, s, _ = x.shape
    y = jnp.einsum("bsd,de->bse", _norm(x), p["uvqk"]) + p["uvqk_b"]
    u, v, q, k = jnp.split(jax.nn.silu(y), 4, axis=-1)
    shape = (b, s, cfg.n_heads, cfg.head_dim)
    return u, v.reshape(shape), q.reshape(shape), k.reshape(shape)


def _output(p, x, o, u):
    """x + f2(LayerNorm(A V) * U), ``o`` the attention output [B,S,h,D]."""
    b, s = x.shape[:2]
    g = _norm(o.reshape(b, s, -1).astype(x.dtype)) * u
    return x + (jnp.einsum("bse,ed->bsd", g, p["out"]) + p["out_b"])


def _causal_attention(p, q, k, v, t, path: str, cfg):
    """Pointwise causal attention of q/k/v [B,n,h,D] with times ``t``
    [B,n], in blocks of QUERY_BLOCK query rows, each over the keys up to
    its last row, so one layer's [h, n, n] scores are never all live at
    once."""
    from repro.kernels.fused_score import ops as fs_ops

    f32 = jnp.float32
    n = q.shape[1]
    outs = []
    for i0 in range(0, n, QUERY_BLOCK):
        i1 = min(n, i0 + QUERY_BLOCK)
        s = jnp.einsum("bqhd,bkhd->bhqk", q[:, i0:i1].astype(f32),
                       k[:, :i1].astype(f32))
        s = s + fs_ops.hstu_relative_bias(
            p["pos_bias"], p["time_bias"], t[:, i0:i1], t[:, :i1],
            offset=i0, path=path)[:, None]
        causal = jnp.arange(i0, i1)[:, None] >= jnp.arange(i1)[None, :]
        a = jnp.where(causal, jax.nn.silu(s), 0.0)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", a, v[:, :i1].astype(f32)))
    return jnp.concatenate(outs, axis=1) / cfg.hstu.max_seq_len


def _encode_kv(params, ids, t, path: str, cfg):
    """Per-layer K/V [L,B,n,h,D] of a causal pass over ``ids`` [B,n]."""
    x = _embed(params, ids, 0, ids.shape[1], cfg)

    def layer(x, p):
        u, v, q, k = _project(p, x, cfg)
        o = _causal_attention(p, q, k, v, t, path, cfg)
        return _output(p, x, o, u), (k, v)
    return scan_or_unroll(layer, x, params["layers"])[1]


def encode_history(params, batch: Dict, cfg: ModelConfig, *,
                   impl: str = "reference"):
    """batch: history [B,n] ids, times [B,n] int32 -> HistoryKV:
    {"k", "v"} [B, L, n, h, D] and "t" [B, n]."""
    t = batch["times"].astype(jnp.int32)
    k, v = _encode_kv(params, batch["history"], t, _kernel_path(impl), cfg)
    return {"k": jnp.moveaxis(k, 1, 0), "v": jnp.moveaxis(v, 1, 0), "t": t}


def _split_stored(entry):
    """(values, scale-or-None) of a K/V leaf, in [L,B,...] layout: a plain
    [B,L,S,H,D] array or a raw ``(values, scale)`` pool view."""
    values, scale = entry if isinstance(entry, tuple) else (entry, None)
    values = jnp.moveaxis(values, 1, 0)
    if scale is not None:
        scale = jnp.moveaxis(scale, 1, 0)
    return values, scale


def _dequant(entry, dtype):
    """A K/V leaf in [L,B,...] layout, dequantized in graph (same formula
    as the pool's ``dequantize_leaf``)."""
    from repro.kernels.fused_score.ref import dequantize_values
    values, scale = _split_stored(entry)
    return dequantize_values(values, scale, dtype)


def _kernel_path(impl: str) -> str:
    """The fused impl takes the kernel where it compiles; the others the
    same attention as one XLA graph."""
    return "auto" if impl == "fused" else "jnp"


def extend_history(params, history_kv, batch: Dict, cfg: ModelConfig, *,
                   prefix_len: int, impl: str = "reference"):
    """Re-encode window positions ``>= prefix_len`` (ids and times from
    ``batch``) against the first ``prefix_len`` positions of a cached
    HistoryKV, K/V and times alike.  ``history_kv`` leaves may be raw pool
    views; the prefix dequantizes in graph.  With ``prefix_len == n``
    nothing re-encodes: the cached entry is returned (dequantized)."""
    from repro.kernels.fused_score import ops as fs_ops

    ids, times = batch["history"], batch["times"]
    n = ids.shape[1]
    if not 0 <= prefix_len <= n:
        raise ValueError(f"prefix_len must be in [0, {n}], got {prefix_len}")
    dtype = params["embed"]["embedding"].dtype
    k_all = _dequant(history_kv["k"], dtype)             # [L,B,n,h,D]
    v_all = _dequant(history_kv["v"], dtype)
    t = jnp.concatenate([history_kv["t"][:, :prefix_len],
                         times[:, prefix_len:]], axis=1).astype(jnp.int32)
    if prefix_len == n:
        return {"k": jnp.moveaxis(k_all, 1, 0),
                "v": jnp.moveaxis(v_all, 1, 0), "t": t}
    p0 = prefix_len
    path = _kernel_path(impl)
    if p0 == 0:
        k_new, v_new = _encode_kv(params, ids, t, path, cfg)
        return {"k": jnp.moveaxis(k_new, 1, 0),
                "v": jnp.moveaxis(v_new, 1, 0), "t": t}
    x = _embed(params, ids[:, p0:], p0, n, cfg)

    def layer(x, inp):
        p, kp, vp = inp
        u, v, q, k = _project(p, x, cfg)
        o = fs_ops.hstu_extend_attention(
            q, kp[:, :p0], vp[:, :p0], k, v,
            fs_ops.hstu_relative_bias(p["pos_bias"], p["time_bias"],
                                      t[:, p0:], t[:, :p0], offset=p0,
                                      path=path),
            fs_ops.hstu_relative_bias(p["pos_bias"], p["time_bias"],
                                      t[:, p0:], t[:, p0:], offset=0,
                                      path=path),
            n_norm=cfg.hstu.max_seq_len, path=path)
        return _output(p, x, o.astype(x.dtype), u), (k, v)

    _, (k_new, v_new) = scan_or_unroll(layer, x,
                                       (params["layers"], k_all, v_all))
    k_full = jnp.concatenate([k_all[:, :, :p0], k_new], axis=2)
    v_full = jnp.concatenate([v_all[:, :, :p0], v_new], axis=2)
    return {"k": jnp.moveaxis(k_full, 1, 0), "v": jnp.moveaxis(v_full, 1, 0),
            "t": t}


def _head(params, x, cfg):
    """Affine LayerNorm -> ``d -> num_tasks`` logits, in f32."""
    h = L.apply_norm(cfg, params["out_norm"], x).astype(jnp.float32)
    return jnp.einsum("bmd,dt->bmt", h,
                      params["head_w"].astype(jnp.float32)) \
        + params["head_b"].astype(jnp.float32)


def score_candidates(params, history_kv, candidates, cfg: ModelConfig, *,
                     impl: str = "reference", row_index=None):
    """Task logits [B,M,T] of candidates [B,M] against a cached HistoryKV
    (plain arrays or raw pool views; ``row_index`` [B] dedup or [B,M]
    packed gather onto its U pool rows).  Every candidate sits at position
    ``n`` at REQUEST_TIME, so a pool row's history bias is one vector per
    layer, computed here and broadcast inside the attention."""
    from repro.kernels.fused_score import ops as fs_ops

    kh, khs = _split_stored(history_kv["k"])             # [L,U,n,h,D]
    vh, vhs = _split_stored(history_kv["v"])
    t_hist = history_kv["t"]                             # [U,n]
    n = kh.shape[2]
    if row_index is not None:
        row_index = jnp.asarray(row_index, jnp.int32)
    b, m = candidates.shape
    x = _embed(params, candidates, n, n + 1, cfg)
    t_req = jnp.full((t_hist.shape[0], 1), REQUEST_TIME, jnp.int32)
    path = _kernel_path(impl)
    has_scale = khs is not None

    def layer(x, inp):
        if has_scale:
            p, kl, vl, ksl, vsl = inp
        else:
            (p, kl, vl), ksl, vsl = inp, None, None
        u, v, q, k = _project(p, x, cfg)
        # the candidates' one bias row per pool row: position n and the
        # request time against each history action's [U,1,n]
        bias = fs_ops.hstu_relative_bias(p["pos_bias"], p["time_bias"],
                                         t_req, t_hist, offset=n, path=path)
        self_bias = p["pos_bias"][0].astype(jnp.float32) \
            + p["time_bias"][0].astype(jnp.float32)
        o = fs_ops.hstu_cached_attention(
            q, kl, vl, k, v, bias, self_bias,
            n_norm=cfg.hstu.max_seq_len, k_scale=ksl, v_scale=vsl,
            row_index=row_index, path=path, bk=HISTORY_BLOCK)
        return _output(p, x, o.astype(x.dtype), u), None

    inp = (params["layers"], kh, vh)
    if has_scale:
        inp = inp + (khs, vhs)
    x, _ = scan_or_unroll(layer, x, inp)
    return _head(params, x, cfg)


def history_kv_specs(params, cfg: ModelConfig, n_history: int,
                     batch: int = 1):
    """ShapeDtypeStruct pytree of the HistoryKV for AOT executor builds."""
    plane = jax.ShapeDtypeStruct((batch, n_history), jnp.int32)
    return jax.eval_shape(
        lambda p, b: encode_history(p, b, cfg), params,
        {"history": plane, "times": plane})


def build_hstu(cfg: ModelConfig) -> ModelBundle:
    if cfg.hstu is None:
        raise ValueError(f"{cfg.name}: family 'hstu' needs an HSTUConfig")

    def init(key):
        return hstu_init(key, cfg)

    def encode_history_fn(params, batch, impl: str = "reference"):
        return encode_history(params, batch, cfg, impl=impl)

    def score_candidates_fn(params, history_kv, candidates,
                            impl: str = "reference", row_index=None):
        return jax.nn.sigmoid(score_candidates(
            params, history_kv, candidates, cfg, impl=impl,
            row_index=row_index))

    def extend_history_fn(params, history_kv, batch, *, prefix_len: int,
                          impl: str = "reference"):
        return extend_history(params, history_kv, batch, cfg,
                              prefix_len=prefix_len, impl=impl)

    def history_kv_specs_fn(params, n_history: int, batch: int = 1):
        return history_kv_specs(params, cfg, n_history, batch)

    def prefill(params, batch, impl: str = "reference", caches=None):
        """Per-candidate task probabilities [B,M,T]: the history encode,
        then the candidates against it."""
        kv = encode_history(params, batch, cfg, impl=impl)
        return score_candidates_fn(params, kv, batch["candidates"],
                                   impl=impl)

    def loss_fn(params, batch, impl: str = "reference"):
        logits = score_candidates(
            params, encode_history(params, batch, cfg, impl=impl),
            batch["candidates"], cfg, impl=impl)
        labels = batch["labels"].astype(jnp.float32)
        ls = jnp.mean(jnp.maximum(logits, 0) - logits * labels
                      + jnp.log1p(jnp.exp(-jnp.abs(logits))))
        return ls, {"bce_loss": ls}

    def decode_step(params, caches, batch, impl: str = "reference"):
        raise NotImplementedError(
            "HSTU scores candidates in one M-FALCON pass; no decode step.")

    def cache_init(batch, max_len, dtype=jnp.bfloat16):
        raise NotImplementedError("HSTU serving keeps no decode cache.")

    def input_specs(shape: ShapeConfig):
        b, n, m = shape.global_batch, shape.seq_len, shape.n_candidates
        specs = {"history": jax.ShapeDtypeStruct((b, n), jnp.int32),
                 "times": jax.ShapeDtypeStruct((b, n), jnp.int32),
                 "candidates": jax.ShapeDtypeStruct((b, m), jnp.int32)}
        if shape.kind == "train":
            specs["labels"] = jax.ShapeDtypeStruct(
                (b, m, cfg.hstu.num_tasks), jnp.float32)
        return specs

    def input_logical(shape: ShapeConfig):
        lg = {"history": ("batch", None), "times": ("batch", None),
              "candidates": ("batch", None)}
        if shape.kind == "train":
            lg["labels"] = ("batch", None, None)
        return lg

    return ModelBundle(cfg, init, loss_fn, prefill, decode_step,
                       input_specs, input_logical, cache_init,
                       encode_history=encode_history_fn,
                       score_candidates=score_candidates_fn,
                       history_kv_specs=history_kv_specs_fn,
                       extend_history=extend_history_fn,
                       host_planes=(HostPlane("history", "window"),
                                    HostPlane("times", "action_times")))
