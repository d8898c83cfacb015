"""RWKV-6 chunked wkv scan — Pallas TPU kernel.

The FKE insight (fuse the hot recurrence into one VMEM-resident kernel)
applied to the attention-free architecture: per (batch x head) the
data-dependent-decay linear-attention recurrence is processed in chunks —
intra-chunk contributions via pairwise log-space decays (always <= 1, so
numerically stable), inter-chunk via a [D, D] state carried in VMEM scratch
across the sequential chunk axis.  MXU work per chunk: [c,D]x[D,D] and
[c,c]x[c,D] GEMMs.

Grid = (BH, n_chunks); chunk axis is innermost/sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret


def _wkv_kernel(r_ref, k_ref, v_ref, wl_ref, u_ref, s0_ref, o_ref, sf_ref,
                state_ref, *, chunk: int, n_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = s0_ref[0]

    r = r_ref[0].astype(jnp.float32)           # [c, D]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    wl = wl_ref[0].astype(jnp.float32)         # log decay, <= 0
    u = u_ref[0].astype(jnp.float32)           # [1, D] -> broadcast
    S = state_ref[...]                         # [D, D]

    la = jnp.cumsum(wl, axis=0)                # inclusive cumulative log decay
    la_prev = la - wl                          # exclusive

    # inter-chunk: o_inter[t] = (r_t * exp(la_prev_t)) @ S
    r_dec = r * jnp.exp(la_prev)
    o_inter = jax.lax.dot_general(r_dec, S, (((1,), (0,)), ((), ())))

    # intra-chunk: scores[t,s] = sum_d r[t,d] k[s,d] exp(la_prev[t,d]-la[s,d]), s<t
    diff = la_prev[:, None, :] - la[None, :, :]              # [c,c,D] <= 0 for s<t
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) > \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    dec = jnp.where(tri[..., None], jnp.exp(diff), 0.0)
    scores = jnp.einsum("td,sd,tsd->ts", r, k, dec)
    o_intra = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())))

    # current-token bonus: (r_t . (u*k_t)) v_t
    bonus = jnp.sum(r * u * k, axis=1, keepdims=True) * v

    o_ref[0] = (o_inter + o_intra + bonus).astype(o_ref.dtype)

    # state update: S' = diag(exp(la_last)) S + sum_s (k_s exp(la_last-la_s)) v_s^T
    la_c = la[-1:]
    k_dec = k * jnp.exp(la_c - la)
    S_new = jnp.exp(la_c[0])[:, None] * S + jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())))
    state_ref[...] = S_new

    @pl.when(ci == n_chunks - 1)
    def _final():
        sf_ref[0] = S_new


def rwkv6_scan_kernel(r, k, v, w_log, u, s0, *, chunk: int = 64,
                      interpret: bool | None = None):
    """r,k,v,w_log [BH, S, D] (S % chunk == 0); u [BH, 1, D]; s0 [BH, D, D].

    Returns (o [BH, S, D], final_state [BH, D, D])."""
    bh, s, d = r.shape
    n_chunks = s // chunk
    kernel = functools.partial(_wkv_kernel, chunk=chunk, n_chunks=n_chunks)
    seq_spec = pl.BlockSpec((1, chunk, d), lambda b, c: (b, c, 0))
    return pl.pallas_call(
        kernel,
        grid=(bh, n_chunks),
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            pl.BlockSpec((1, 1, d), lambda b, c: (b, 0, 0)),    # u
            pl.BlockSpec((1, d, d), lambda b, c: (b, 0, 0)),    # s0
        ],
        out_specs=[
            seq_spec,
            pl.BlockSpec((1, d, d), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), r.dtype),
            jax.ShapeDtypeStruct((bh, d, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        interpret=default_interpret() if interpret is None else interpret,
    )(r, k, v, w_log, u, s0)
