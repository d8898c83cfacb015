"""Pallas TPU kernels — the Fused Kernel Engine (FKE) compute layer.

The paper's FKE fuses (a) mask-aware Flash-Attention (SUMI candidate mask)
and (b) LayerNorm+FFN into TensorRT plug-ins.  Here each hot-spot is a Pallas
kernel in its own subpackage with the framework triple:

  kernel.py  pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py     jit'd public wrapper (padding, layout, interpret-mode fallback)
  ref.py     pure-jnp oracle used by the allclose test sweeps

Kernels: flash_attention (causal/sliding/full/SUMI masks with block skipping),
fused_ffn (norm + W1(+gate) + act + W2, f32 VMEM accumulator), fused_score
(the FKE cached-candidate scoring engine: two-segment SUMI/extend attention
reading quantized pool KV and the dedup row index in-kernel), rwkv6_scan
(chunked data-dependent-decay linear attention for the attention-free arch).

Off the TPU (``JAX_PLATFORMS=cpu``) kernels execute under
``interpret=True``; on TPU the same BlockSpecs drive the real pipeline
emitter (HBM->VMEM double buffering against the MXU — the TPU analogue of
the paper's cp_async GEMM pipelining).  :func:`default_interpret` is the
one place that decides: every ``pallas_call`` whose caller passes
``interpret=None`` asks it, and ``fused_score``'s ``path="auto"`` picks
the kernel exactly where it would not be interpreted.
"""

import jax


def default_interpret() -> bool:
    """interpret=True unless running on real TPU hardware (decided from
    the default backend when a kernel is traced)."""
    return jax.default_backend() != "tpu"
