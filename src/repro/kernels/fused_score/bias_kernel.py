"""HSTU's pairwise relative bias, time part — Pallas TPU kernel.

The encode and extend paths add ``w_pos[i - j] + w_time[bucket(t_i -
t_j)]`` to every score of a query block.  The time part needs a table
read per pair, and an XLA gather from a small table runs about one
element at a time on the TPU (seconds for one 4,096-action encode).  Here
each [bq, 128] tile computes its pairs' buckets from the two time vectors
and reads the table (one 128-lane row) with a lane gather; the position
part arrives as a [Q, K] matrix ops.py builds from row slices.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import default_interpret

#: lanes of a TPU vector register: the time table's width in the gather
LANES = 128
#: width of one HSTU time bucket in ln-seconds (the authors' 0.301)
TIME_BUCKET_WIDTH = 0.301


def _time_bias_kernel(tq_ref, tk_ref, tab_ref, pos_ref, o_ref, *,
                      max_bucket: int):
    """One [bq, bk] tile of an HSTU relative bias: the position part as
    given, plus the time table read at each pair's bucket by a lane
    gather (the table fits one 128-lane row)."""
    dt = tq_ref[0] - tk_ref[0]                               # [bq, bk]
    gap = jnp.maximum(jnp.abs(dt), 1).astype(jnp.float32)
    bucket = jnp.minimum(jnp.floor(jnp.log(gap) / TIME_BUCKET_WIDTH),
                         max_bucket).astype(jnp.int32)
    table = jnp.broadcast_to(tab_ref[...], (bucket.shape[0], LANES))
    o_ref[0] = pos_ref[...] + jnp.take_along_axis(table, bucket, axis=1)


def time_bias_kernel(t_q, t_k, table, pos, *, max_bucket: int, bq: int,
                     interpret: bool | None = None):
    """``pos + table[bucket(t_q - t_k)]`` [B, Qp, Kp] f32: ``t_q`` [B, Qp, 1]
    and ``t_k`` [B, 1, Kp] int32 times, ``table`` [1, 128] f32, ``pos``
    [Qp, Kp] f32; Qp a multiple of ``bq`` and Kp of 128 (ops.py pads)."""
    b, qp, _ = t_q.shape
    kp = t_k.shape[2]
    kernel = functools.partial(_time_bias_kernel, max_bucket=max_bucket)
    return pl.pallas_call(
        kernel,
        grid=(b, qp // bq, kp // LANES),
        in_specs=[pl.BlockSpec((1, bq, 1), lambda i, qi, kj: (i, qi, 0)),
                  pl.BlockSpec((1, 1, LANES), lambda i, qi, kj: (i, 0, kj)),
                  pl.BlockSpec((1, LANES), lambda i, qi, kj: (0, 0)),
                  pl.BlockSpec((bq, LANES), lambda i, qi, kj: (qi, kj))],
        out_specs=pl.BlockSpec((1, bq, LANES),
                               lambda i, qi, kj: (i, qi, kj)),
        out_shape=jax.ShapeDtypeStruct((b, qp, kp), jnp.float32),
        interpret=default_interpret() if interpret is None else interpret,
    )(t_q, t_k, table, pos)
