"""The pointwise (HSTU) kernel's cached mode: wide q blocks over packed rows.

A cached call scores each q block of ``bq`` rows (up to 128) against the
pooled history of its pool row.  Under segment packing the row may change
every ``bs`` rows (the packer's alignment, 8), so the block is made of
``bq // bs`` sub-blocks with a pool row each, prefetched as ``row_index``
[B, Mp // bs].  The kernel decides per block, at run time, by
``ops.qblock_uniform``: a block whose sub-blocks all read one row streams
that row's history once for all ``bq`` rows; any other block streams each
sub-block's row for its ``bs`` rows, as a grid of ``bs``-row blocks would.

The history K/V and bias stay in HBM and are copied block by block
(``bk`` positions) into two VMEM slots, so the number of blocks read is
decided in the kernel and not by the grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pointwise_cached_kernel(idx_ref, ks_ref, vs_ref, sb_ref, q_ref, kh_hbm,
                             vh_hbm, bh_hbm, kc_ref, vc_ref, o_ref, qf_ref,
                             acc_ref, k_buf, v_buf, b_buf, sem, seq_ref, *,
                             h: int, g: int, bq: int, bs: int, bk: int,
                             hist_steps: int, n_norm: float):
    """Cached pointwise scoring of one wide q block of ``bq`` rows, made of
    ``bq // bs`` sub-blocks of ``bs`` rows with a pool row each
    (``idx_ref`` [B, Mp // bs]).  Where every sub-block reads one pool row
    (``ops.qblock_uniform``) the whole block scores against that row's
    history, each K/V/bias block fetched once; otherwise each sub-block
    scores against its own row's, as a block of ``bs`` rows would.  Either
    way a row adds its history blocks in order, then its self key, each
    with the arithmetic of the extend kernel's steps (``kernel.py``).

    The history operands stay in HBM; the kernel copies [bk] blocks of them
    into two VMEM slots, the next block's copy running under the current
    block's compute.  The last block of a grid step starts the next step's
    first copy (sub-block 0's row, block 0, in both branches), so the copy
    sequence runs on across grid steps; ``seq_ref`` counts blocks to name
    the slot."""
    from repro.kernels.fused_score.ops import qblock_uniform

    bh = pl.program_id(0)
    qi = pl.program_id(1)
    n_bh = pl.num_programs(0)
    nq = pl.num_programs(1)
    n_sub = bq // bs
    kvh = (bh % h) // g                      # kv head of this q head

    def row_of(bh_, qi_, j):
        return idx_ref[bh_ // h, qi_ * n_sub + j]

    def copies(row, head, kblk, slot):
        cols = pl.ds(kblk * bk, bk)
        return (pltpu.make_async_copy(kh_hbm.at[row, head, cols],
                                      k_buf.at[slot], sem.at[0, slot]),
                pltpu.make_async_copy(vh_hbm.at[row, head, cols],
                                      v_buf.at[slot], sem.at[1, slot]),
                pltpu.make_async_copy(bh_hbm.at[row, :, cols],
                                      b_buf.at[slot], sem.at[2, slot]))

    def start(row, head, kblk, slot):
        for c in copies(row, head, kblk, slot):
            c.start()

    @pl.when((bh == 0) & (qi == 0))
    def _first_copy():
        seq_ref[0] = 0
        start(row_of(0, 0, 0), 0, 0, 0)

    last_q = qi == nq - 1
    nxt_bh = jnp.where(last_q, bh + 1, bh)
    nxt_qi = jnp.where(last_q, 0, qi + 1)
    has_next = nxt_bh < n_bh

    qf_ref[...] = q_ref[0, 0].astype(jnp.float32)           # [bq, D]
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def history(q, row, slot):
        """SiLU(q.k * k_scale + bias) @ v * v_scale over one block.  No
        mask: padded history positions hold zero K, V and bias, so they add
        SiLU(0) * 0 = 0, and padded query rows are cut off by ops.py."""
        k = k_buf[slot].astype(jnp.float32)                  # [bk, D]
        v = v_buf[slot].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        s = s * ks_ref[row, kvh] + b_buf[slot]
        pv = jax.lax.dot_general(jax.nn.silu(s), v,
                                 (((1,), (0,)), ((), ())))
        return pv * vs_ref[row, kvh]

    def stream(n_items, item, compute):
        """Blocks ``item(t)`` = (row, kblk) for t < n_items, each waited
        for, the next one's copy started, then ``compute``d."""
        def body(t, carry):
            slot = seq_ref[0] % 2
            for c in copies(0, 0, 0, slot):
                c.wait()

            @pl.when(t + 1 < n_items)
            def _next_block():
                row, kblk = item(t + 1)
                start(row, kvh, kblk, 1 - slot)

            @pl.when((t + 1 == n_items) & has_next)
            def _next_step():
                start(row_of(nxt_bh, nxt_qi, 0), (nxt_bh % h) // g, 0,
                      1 - slot)

            compute(t, item(t)[0], slot)
            seq_ref[0] = seq_ref[0] + 1
            return carry

        jax.lax.fori_loop(0, n_items, body, 0)

    uniform = qblock_uniform(lambda j: row_of(bh, qi, j), n_sub)

    @pl.when(uniform)
    def _wide():
        row = row_of(bh, qi, 0)

        def compute(t, row, slot):
            acc_ref[...] = acc_ref[...] + history(qf_ref[...], row, slot)

        stream(hist_steps, lambda t: (row, t), compute)

    @pl.when(jnp.logical_not(uniform))
    def _per_sub_block():
        def compute(t, row, slot):
            r = pl.ds(pl.multiple_of((t // hist_steps) * bs, bs), bs)
            acc_ref[r] = acc_ref[r] + history(qf_ref[r], row, slot)

        stream(n_sub * hist_steps,
               lambda t: (row_of(bh, qi, t // hist_steps), t % hist_steps),
               compute)

    # the self key: query i sees candidate i alone (SUMI), the diagonal
    k = kc_ref[0, 0].astype(jnp.float32)                     # [bq, D]
    v = vc_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(qf_ref[...], k, (((1,), (1,)), ((), ())))
    s = s + sb_ref[0]
    diag = (jax.lax.broadcasted_iota(jnp.int32, (bq, bq), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (bq, bq), 1))
    p = jnp.where(diag, jax.nn.silu(s), 0.0)
    acc_ref[...] = acc_ref[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    o_ref[0, 0] = (acc_ref[...] / n_norm).astype(o_ref.dtype)


def hstu_cached_call(row_index, k_scale, v_scale, self_bias, q, k_hist,
                     v_hist, bias_hist, k_cand, v_cand, *, h, g, bq, bs, bk,
                     hist_steps, n_norm, interpret):
    """The cached mode's ``pallas_call``: grid (B*H, Mp // bq), q and
    candidate blocks pipelined by BlockSpec, the history operands left in
    HBM for :func:`_pointwise_cached_kernel` to copy."""
    b, _, mp, d = q.shape
    kernel = functools.partial(
        _pointwise_cached_kernel, h=h, g=g, bq=bq, bs=bs, bk=bk,
        hist_steps=hist_steps, n_norm=float(n_norm))

    def q_map(bh, qi, idx_ref, ks_ref, vs_ref, sb_ref):
        return (bh // h, bh % h, qi, 0)

    def kc_map(bh, qi, idx_ref, ks_ref, vs_ref, sb_ref):
        return (bh // h, (bh % h) // g, qi, 0)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,     # row_index, k_scale, v_scale, self_bias
        grid=(b * h, mp // bq),
        in_specs=[pl.BlockSpec((1, 1, bq, d), q_map), hbm, hbm, hbm,
                  pl.BlockSpec((1, 1, bq, d), kc_map),
                  pl.BlockSpec((1, 1, bq, d), kc_map)],
        out_specs=pl.BlockSpec((1, 1, bq, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),                 # q in f32
            pltpu.VMEM((bq, d), jnp.float32),                 # acc
            pltpu.VMEM((2, bk, d), k_hist.dtype),             # K slots
            pltpu.VMEM((2, bk, d), v_hist.dtype),             # V slots
            pltpu.VMEM((2, 1, bk), jnp.float32),              # bias slots
            pltpu.SemaphoreType.DMA((3, 2)),
            pltpu.SMEM((1,), jnp.int32),                      # blocks so far
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        # the copy sequence runs across grid steps, in grid order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(row_index, k_scale, v_scale, self_bias, q, k_hist, v_hist, bias_hist,
      k_cand, v_cand)
