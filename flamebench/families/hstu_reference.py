"""Plain HSTU reference: the full causal forward of one row's history and
candidates in float32 ``jax.numpy`` at ``highest``; no cache, no pool, no
kernel, no packing.

HSTU (Zhai et al., ICML 2024, arXiv:2402.17152; the authors' code,
github.com/facebookresearch/generative-recommenders) as the configuration
states it.  One sequence per row: the ``n`` window actions at positions
``0..n-1`` and times ``t_0..t_{n-1}``, then the candidates, each at
position ``n`` and the request time.  A history action sees the actions up
to itself; a candidate sees the whole history and itself, never another
candidate (M-FALCON).  Each layer, on ``X`` of width ``d``:

* ``U, V, Q, K = split(SiLU(LayerNorm(X) @ W1 + b1))`` (``h`` heads of
  ``head_dim`` each);
* ``A_ij = SiLU(Q_i . K_j + w_pos[p_i - p_j] + w_time[bucket(t_i - t_j)])
  / N`` where the mask allows, ``N`` the configured sequence length,
  ``bucket(x) = min(floor(ln(max(|x|, 1)) / 0.301), num_time_buckets)``;
* ``Y = X + (LayerNorm(A V) * U) @ W2 + b2``.

The input is ``sqrt(d) * embedding + learned absolute position``; the
LayerNorms carry no scale or bias (eps 1e-6).  A candidate's last output
goes through an affine LayerNorm (eps 1e-5) and ``d -> num_tasks``; the
served score is the sigmoid.

Departures from the authors' code, all shared with the program:

* a history action's query time is its own time; the authors' uses the
  next action's, which would make the last cached token stale on every
  request;
* the ranking head (affine LayerNorm, linear, sigmoid) stands in for the
  authors' retrieval output (L2-normalized dot product with the catalog);
* the position bias table holds offsets ``0..N`` only (the mask never
  reads a negative offset).

Action times come from :func:`action_times`, a copy of the rule of the
program's synthetic user-history service, kept here so that no program
change moves the yardstick.  It imports nothing of the program.
``lowp=True`` is the control: every matrix-product operand rounds to
float8 (e4m3) first, the precision step below the served bfloat16.
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np

LN_EPS = 1e-6
HEAD_LN_EPS = 1e-5
TIME_BUCKET_WIDTH = 0.301
#: the request time of every request, on the service's clock (seconds)
REQUEST_TIME = 1_700_000_000
MAX_AGE_S = 1 << 26
#: query rows per block: one block's scores are [h, QUERY_BLOCK, n + M]
QUERY_BLOCK = 256


def action_times(user_id: Optional[int], items: np.ndarray) -> np.ndarray:
    """int32 times of a user's actions on ``items``: a splitmix64 hash of
    (user id, item id) picks each age, log-uniform below MAX_AGE_S, before
    REQUEST_TIME."""
    with np.errstate(over="ignore"):
        x = np.asarray(items).astype(np.uint64) ^ (
            np.uint64((user_id or 0) & 0xFFFFFFFFFFFFFFFF)
            * np.uint64(0x9E3779B97F4A7C15))
        x = x + np.uint64(0x9E3779B97F4A7C15)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        x = x ^ (x >> np.uint64(31))
    u = (x >> np.uint64(11)).astype(np.float64) * 2.0 ** -53
    age = np.floor(np.exp(u * np.log(MAX_AGE_S))).astype(np.int64)
    return (REQUEST_TIME - age).astype(np.int32)


def _forward(params, ids, times, cands, *, model, lowp, block):
    """Task probabilities [M, T] of one row: ``ids``/``times`` [n],
    ``cands`` [M]."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    if lowp:
        def q8(x):
            return x.astype(jnp.float8_e4m3fn).astype(f32)
    else:
        def q8(x):
            return x

    def mm(spec, a, b):
        return jnp.einsum(spec, q8(a.astype(f32)), q8(b.astype(f32)))

    c = model["hstu"]
    n_norm = float(c["max_seq_len"])
    h, hd, d = model["n_heads"], model["head_dim"], model["d_model"]
    n, m = ids.shape[0], cands.shape[0]
    s_all = n + m
    table = params["embed"]["embedding"]
    pos_table = params["pos_embed"].astype(f32)

    def norm(x, eps=LN_EPS):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps)

    pos = jnp.concatenate([jnp.arange(n), jnp.full((m,), n)])
    t = jnp.concatenate([times.astype(jnp.int32),
                         jnp.full((m,), REQUEST_TIME, jnp.int32)])
    x = jnp.take(table, jnp.concatenate([ids, cands]), axis=0).astype(f32) \
        * np.sqrt(d) + pos_table[pos]
    col = jnp.arange(s_all)
    n_pos = c["max_seq_len"] + 1
    n_buckets = c["num_time_buckets"] + 1

    def rows_of(r):
        """Mask, position offsets and time buckets of query rows ``r``: a
        history row sees the actions up to itself, a candidate row the
        history and itself."""
        mask = jnp.where(r[:, None] < n, col[None, :] <= r[:, None],
                         (col[None, :] < n) | (col[None, :] == r[:, None]))
        dt = jnp.maximum(jnp.abs(t[r][:, None] - t[None, :]), 1)
        bucket = jnp.minimum(
            jnp.floor(jnp.log(dt.astype(f32)) / TIME_BUCKET_WIDTH),
            c["num_time_buckets"]).astype(jnp.int32)
        return mask, pos[r], bucket

    def bias(w_pos, w_time, pos_r, bucket):
        """``w_pos[clip(p_i - p_j, 0, N)] + w_time[bucket]`` of a block of
        rows: the history keys' offsets are one slice of the reversed
        position table per row (the candidates all sit at offset 0 or
        below), and the time table is read by selecting, not gathering
        (an XLA gather runs one element at a time on the TPU)."""
        ext = jnp.concatenate([w_pos[::-1], jnp.full((n,), w_pos[0])])
        hist = jax.vmap(lambda p: jax.lax.dynamic_slice(
            ext, (n_pos - 1 - p,), (n,)))(pos_r)
        b_pos = jnp.concatenate(
            [hist, jnp.full((pos_r.shape[0], m), w_pos[0])], axis=1)
        b_time = jnp.sum(jnp.where(bucket[..., None] == jnp.arange(n_buckets),
                                   w_time, 0.0), axis=-1)
        return b_pos + b_time

    qb = block or s_all
    n_blk = -(-s_all // qb)
    pad = n_blk * qb - s_all

    def layer(x, p):
        y = jax.nn.silu(mm("sd,de->se", norm(x), p["uvqk"])
                        + p["uvqk_b"].astype(f32))
        u, v, q, k = jnp.split(y, 4, axis=-1)
        v = v.reshape(s_all, h, hd)
        q = jnp.pad(q.reshape(s_all, h, hd), ((0, pad), (0, 0), (0, 0)))
        k = k.reshape(s_all, h, hd)
        w_pos = p["pos_bias"].astype(f32)
        w_time = p["time_bias"].astype(f32)

        def one_block(i):
            r = jax.lax.dynamic_slice_in_dim(jnp.arange(n_blk * qb), i * qb,
                                             qb)
            mask, pos_r, bucket = rows_of(jnp.minimum(r, s_all - 1))
            qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
            s = mm("qhd,khd->hqk", qi, k) \
                + bias(w_pos, w_time, pos_r, bucket)[None]
            a = jnp.where(mask[None], jax.nn.silu(s), 0.0) / n_norm
            return mm("hqk,khd->qhd", a, v)

        o = jax.lax.map(one_block, jnp.arange(n_blk))
        o = o.reshape(n_blk * qb, h * hd)[:s_all]
        g = norm(o) * u
        return x + mm("se,ed->sd", g, p["out"]) + p["out_b"].astype(f32), \
            None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    out = x[n:]
    hn = norm(out, HEAD_LN_EPS) * params["out_norm"]["scale"].astype(f32) \
        + params["out_norm"]["bias"].astype(f32)
    logits = mm("md,dt->mt", hn, params["head_w"]) \
        + params["head_b"].astype(f32)
    return jax.nn.sigmoid(logits)


@functools.lru_cache(maxsize=None)
def _compiled(model_key, lowp: bool, block: Optional[int]):
    import jax

    fn = functools.partial(_forward, model=_thaw(model_key), lowp=lowp,
                           block=block)
    return jax.jit(fn)


def _freeze(d):
    if isinstance(d, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in d.items()))
    return d


def _thaw(t):
    if isinstance(t, tuple) and t and all(
            isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], str)
            for x in t):
        return {k: _thaw(v) for k, v in t}
    return t


def scores(params, model: dict, ids, times, cands, *, lowp: bool = False,
           block: Optional[int] = QUERY_BLOCK) -> np.ndarray:
    """Task probabilities [M, T] of one row: window ids and action times
    [n], candidates [M]; the queries in blocks of ``block`` rows (None:
    all at once)."""
    import jax

    fn = _compiled(_freeze(model), bool(lowp), block)
    with jax.default_matmul_precision("highest"):
        out = fn(params, np.asarray(ids, np.int32),
                 np.asarray(times, np.int32), np.asarray(cands, np.int32))
    return np.asarray(out)
