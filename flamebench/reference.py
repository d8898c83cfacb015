"""Plain Climber reference: one float32 ``jax.numpy`` forward, no kernels,
no cache, no pool, no packing.

Climber (arXiv:2502.09888; served by FLAME, arXiv:2509.22681) as this
repository serves it:

* the history window (the first ``n_history`` ids) splits into
  ``num_blocks`` sub-sequences of ``w`` items; block ``i`` reads item
  embeddings plus learned positions ``0..w-1``, then one side token (the
  12 side features times ``side_proj``) at position ``w``;
* generated tokens (top-k decode) follow the side token, item embeddings
  only, at positions ``w+1, w+2, ...``: the prefix is causal;
* each candidate sits at the position after the valid prefix and attends to
  the whole valid prefix and to itself, never to another candidate (SUMI);
* pre-norm layers (LayerNorm, eps 1e-5): RoPE (theta from the config) on q
  and k, q divided by the layer's temperature softplus(t) + 0.5, scores
  scaled by 1/sqrt(head_dim), out-projection, residual, LayerNorm, GELU
  (tanh form) feed-forward, residual;
* per-candidate block outputs fuse by a per-dimension softmax gate over
  blocks, LayerNorm, then a multi-gate mixture of experts head: one logit
  per task, and the served score is its sigmoid.

It imports nothing of the program.  ``lowp=True`` is the control: every
matrix-product operand rounds to float8 (e4m3) first, the precision step
below the served bfloat16.
"""
from __future__ import annotations

import functools

import numpy as np

LN_EPS = 1e-5


def side_features(history: np.ndarray) -> np.ndarray:
    """The synthetic feature service's side vector for one request: the
    mean over the history's distinct item ids of each id's 12 standard
    normals (a generator seeded by the id)."""
    ids = dict.fromkeys(int(i) for i in history)
    rows = [np.random.default_rng(i & 0x7FFFFFFF).standard_normal(
        12, dtype=np.float32) for i in ids]
    return np.mean(rows, axis=0).astype(np.float32)


def _forward(params, hist, side, gen, gen_len, cands, *, model, lowp):
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

    c = model["climber"]
    nb = c["num_blocks"]
    hd = model["head_dim"]
    theta = float(model["rope_theta"])
    table = params["embed"]["embedding"]
    bsz, n = hist.shape
    w = n // nb
    g = gen.shape[1]
    mp = cands.shape[1]
    p_len = w + 1 + g
    valid = w + 1 + gen_len                              # [B]

    def emb(ids):
        return jnp.take(table, ids, axis=0).astype(f32)

    def ln(x, p):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + LN_EPS) * p["scale"].astype(f32) \
            + p["bias"].astype(f32)

    def gelu(x):
        return 0.5 * x * (1.0 + jnp.tanh(
            np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))

    def rope(x, pos):                                    # x [B,S,H,D]
        d2 = x.shape[-1] // 2
        freqs = jnp.exp(-np.log(theta) * jnp.arange(d2, dtype=f32) / d2)
        ang = pos[..., None].astype(f32) * freqs         # [B,S,d2]
        cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
        x1, x2 = x[..., :d2], x[..., d2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1)

    s_all = p_len + mp
    row = jnp.arange(s_all)
    pos = jnp.where(row[None] < p_len, row[None], valid[:, None])  # [B,S]
    in_prefix = row[None, None, :] < valid[:, None, None]          # cols
    causal = row[None, :, None] >= row[None, None, :]
    mask = jnp.where(row[None, :, None] < p_len, causal,
                     in_prefix | (row[None, :, None] == row[None, None, :]))

    side_tok = mm("bf,fd->bd", side, params["side_proj"])[:, None]
    gen_emb = emb(gen)
    cand_emb = emb(cands)
    outs = []
    for i in range(nb):
        bp = params["blocks"][f"b{i}"]
        items = emb(hist[:, i * w:(i + 1) * w]) \
            + params["pos_embed"][:w].astype(f32)[None]
        x = jnp.concatenate([items, side_tok, gen_emb, cand_emb], axis=1)
        for layer in range(c["layers_per_block"]):
            p = jax.tree.map(lambda a: a[layer], bp)
            h = ln(x, p["norm1"])
            q = rope(mm("bsd,dhk->bshk", h, p["attn"]["wq"]), pos)
            k = rope(mm("bsd,dhk->bshk", h, p["attn"]["wk"]), pos)
            v = mm("bsd,dhk->bshk", h, p["attn"]["wv"])
            tau = jax.nn.softplus(p["temp"][0].astype(f32)) + 0.5
            s = mm("bqhd,bkhd->bhqk", q / tau, k) / np.sqrt(hd)
            s = jnp.where(mask[:, None], s, -jnp.inf)
            a = jax.nn.softmax(s, axis=-1)
            o = mm("bhqk,bkhd->bqhd", a, v)
            x = x + mm("bshk,hkd->bsd", o, p["attn"]["wo"])
            h2 = ln(x, p["norm2"])
            x = x + mm("bsf,fd->bsd",
                       gelu(mm("bsd,df->bsf", h2, p["ffn"]["w_up"])),
                       p["ffn"]["w_down"])
        outs.append(x[:, p_len:])
    hb = jnp.stack(outs, axis=2)                         # [B,M,Nb,d]
    gl = hb * params["gate_w"].astype(f32) + params["gate_b"].astype(f32)
    fused = (jax.nn.softmax(gl, axis=2) * hb).sum(2)
    fused = ln(fused, params["out_norm"])
    e1 = gelu(mm("bmd,edh->bmeh", fused, params["experts_w1"]))
    e2 = mm("bmeh,ehg->bmeg", e1, params["experts_w2"])
    tg = jax.nn.softmax(mm("bmd,tde->bmte", fused, params["task_gates"]), -1)
    mix = mm("bmte,bmeg->bmtg", tg, e2)
    logits = mm("bmtg,tg->bmt", mix, params["task_towers"])
    return jax.nn.sigmoid(logits)


@functools.lru_cache(maxsize=None)
def _compiled(model_key, lowp: bool):
    import jax

    model = _thaw(model_key)
    fn = functools.partial(_forward, model=model, lowp=lowp)
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


def scores(params, model: dict, hist, side, gen, gen_len, cands, *,
           lowp: bool = False) -> np.ndarray:
    """Task probabilities [B, M, T] for candidate rows ``cands`` [B, M]
    after history windows ``hist`` [B, n], side vectors ``side`` [B, 12]
    and ``gen_len`` [B] valid generated tokens of ``gen`` [B, G]."""
    import jax

    fn = _compiled(_freeze(model), bool(lowp))
    with jax.default_matmul_precision("highest"):
        out = fn(params, np.asarray(hist, np.int32),
                 np.asarray(side, np.float32), np.asarray(gen, np.int32),
                 np.asarray(gen_len, np.int32), np.asarray(cands, np.int32))
    return np.asarray(out)
