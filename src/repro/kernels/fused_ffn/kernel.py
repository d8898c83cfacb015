"""Fused RMSNorm + FFN — Pallas TPU kernel (the FKE "fused-FFN plug-in").

One kernel computes  out = act(norm(x) @ W_up [, * silu(norm(x) @ W_gate)]) @ W_down
without round-tripping the normalized activations or the [T, d_ff] hidden
through HBM.  Grid = (token blocks, d_ff blocks); the d_ff axis is the
sequential inner axis:

  fj == 0   : normalize the x block once into VMEM scratch
  every fj  : [bt, d] x [d, bf] up/gate GEMMs on the MXU, activation,
              [bt, bf] x [bf, d] partial down GEMM accumulated in f32 scratch
  fj == last: cast + write the output block

VMEM working set per step: x/xn blocks (bt x d), W slices (d x bf + bf x d),
f32 accumulator (bt x d) — block sizes chosen in ops.py so this fits ~16MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret


def _ffn_kernel(x_ref, scale_ref, wu_ref, wg_ref, wd_ref, o_ref,
                xn_ref, acc_ref, *, activation: str, nf: int, eps: float,
                has_norm: bool):
    fj = pl.program_id(1)

    @pl.when(fj == 0)
    def _init():
        x = x_ref[...].astype(jnp.float32)
        if has_norm:
            var = jnp.mean(x * x, axis=-1, keepdims=True)
            x = x * jax.lax.rsqrt(var + eps) * \
                (1.0 + scale_ref[0].astype(jnp.float32))
        xn_ref[...] = x
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xn = xn_ref[...]
    up = jax.lax.dot_general(xn, wu_ref[...].astype(jnp.float32),
                             (((1,), (0,)), ((), ())))
    if activation == "swiglu":
        gate = jax.lax.dot_general(xn, wg_ref[...].astype(jnp.float32),
                                   (((1,), (0,)), ((), ())))
        act = jax.nn.silu(gate) * up
    elif activation == "gelu":
        act = jax.nn.gelu(up)
    else:
        act = jax.nn.relu(up)
    acc_ref[...] += jax.lax.dot_general(act, wd_ref[...].astype(jnp.float32),
                                        (((1,), (0,)), ((), ())))

    @pl.when(fj == nf - 1)
    def _finalize():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def fused_ffn_kernel(x, norm_scale, w_up, w_gate, w_down, *,
                     activation: str = "swiglu", has_norm: bool = True,
                     bt: int = 256, bf: int = 512, eps: float = 1e-6,
                     interpret: bool | None = None):
    """x [T, d] (T % bt == 0, f % bf == 0 — padded by ops.py)."""
    t, d = x.shape
    f = w_up.shape[1]
    nt, nf = t // bt, f // bf
    kernel = functools.partial(_ffn_kernel, activation=activation, nf=nf,
                               eps=eps, has_norm=has_norm)
    return pl.pallas_call(
        kernel,
        grid=(nt, nf),
        in_specs=[
            pl.BlockSpec((bt, d), lambda ti, fj: (ti, 0)),     # x
            pl.BlockSpec((1, d), lambda ti, fj: (0, 0)),       # norm scale
            pl.BlockSpec((d, bf), lambda ti, fj: (0, fj)),     # w_up slice
            pl.BlockSpec((d, bf), lambda ti, fj: (0, fj)),     # w_gate slice
            pl.BlockSpec((bf, d), lambda ti, fj: (fj, 0)),     # w_down slice
        ],
        out_specs=pl.BlockSpec((bt, d), lambda ti, fj: (ti, 0)),
        out_shape=jax.ShapeDtypeStruct((t, d), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bt, d), jnp.float32),                  # normalized x
            pltpu.VMEM((bt, d), jnp.float32),                  # f32 accumulator
        ],
        interpret=default_interpret() if interpret is None else interpret,
    )(x, norm_scale, w_up, w_gate, w_down)
