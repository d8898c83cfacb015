"""The pointwise (HSTU) variant of ``fused_score``: the Pallas kernel in
interpret mode against its jnp path and the dense fp32 oracle.

1. masks, bitwise — with integer queries and keys, dyadic biases, unit
   scales and one-hot values (history position ``j`` writes lane ``j``,
   the self/suffix keys the lanes after), every output lane is exactly
   one ``SiLU(s + bias) / N`` weight, so the kernel and the jnp path must
   agree to the bit on the cached, packed and extend masks, whatever order
   they sum in, and each must read the same weight matrix as the oracle;
2. random operands — int8 and native stored K/V, 1-D dedup and 2-D packed
   row indices, several history blocks, against the oracle at fp32
   rounding;
3. the relative bias of the encode and extend paths: the lane-gather
   kernel reads exactly the table entries an XLA gather reads.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fused_score import ops as fs_ops
from repro.kernels.fused_score import ref as fs_ref
from repro.serving.kv_cache import quantize_leaf

N_NORM = 64.0          # a power of two: the final scaling is exact
TOL = 2e-5             # fp32 reassociation of the dots and the sums


def _onehot_values(n_keys: int, d: int, offset: int = 0):
    """[n_keys, d] with key j writing 1.0 to lane offset + j."""
    v = np.zeros((n_keys, d), np.float32)
    v[np.arange(n_keys), offset + np.arange(n_keys)] = 1.0
    return v


def _exact_case(seed, *, b, m, h, s, u, d=64, store="native"):
    """Operands whose products and sums before the SiLU are exact."""
    rng = np.random.default_rng(seed)
    q = rng.integers(-2, 3, (b, m, h, d)).astype(np.float32)
    kh = rng.integers(-2, 3, (u, s, h, d)).astype(np.float32)
    kc = rng.integers(-2, 3, (b, m, h, d)).astype(np.float32)
    vh = np.broadcast_to(_onehot_values(s, d)[None, :, None, :],
                         (u, s, h, d)).copy()
    # candidate i's self value writes lane s + i (wrapped past lane d - 1:
    # a cached row reads one self key, so lanes need only differ from the
    # history's)
    vc = np.zeros((m, d), np.float32)
    vc[np.arange(m), s + np.arange(m) % (d - s)] = 1.0
    vc = np.broadcast_to(vc[None, :, None, :], (b, m, h, d)).copy()
    ks = vs = None
    if store == "int8":
        kh, vh = kh.astype(np.int8), vh.astype(np.int8)
        ks = vs = np.full((u, 1, h, 1), 127.0, np.float32)   # scale 1.0
    return dict(q=q, k_hist=kh, v_hist=vh, k_cand=kc, v_cand=vc,
                k_scale=ks, v_scale=vs), rng


def _dyadic(rng, shape):
    return (rng.integers(-16, 17, shape) / 8.0).astype(np.float32)


@pytest.mark.parametrize("store", ["native", "int8"])
@pytest.mark.parametrize("bk", [8, 128])
def test_cached_mask_bitwise(store, bk):
    """Dedup rows: every candidate reads its pool row's whole history and
    its own key; several history blocks at bk=8."""
    t, rng = _exact_case(1, b=3, m=12, h=2, s=40, u=2, store=store)
    bias = _dyadic(rng, (2, 1, 40))
    idx = np.asarray([1, 0, 1], np.int32)
    kw = dict(n_norm=N_NORM, k_scale=t["k_scale"], v_scale=t["v_scale"],
              row_index=idx, bk=bk)
    args = (t["q"], t["k_hist"], t["v_hist"], t["k_cand"], t["v_cand"],
            bias, 0.375)
    got = fs_ops.hstu_cached_attention(*args, path="kernel", **kw)
    want = fs_ops.hstu_cached_attention(*args, path="jnp", **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    oracle = fs_ref.pointwise_reference(
        *args[:6], n_norm=N_NORM, self_bias=0.375, k_scale=t["k_scale"],
        v_scale=t["v_scale"], row_index=idx)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(oracle))


def test_packed_mask_bitwise():
    """A 2-D seg index with 8-aligned segments (the packer's contract):
    each segment reads its own user's history, never another's."""
    t, rng = _exact_case(2, b=2, m=24, h=2, s=36, u=3)
    bias = _dyadic(rng, (3, 1, 36))
    seg = np.asarray([[0] * 8 + [2] * 8 + [1] * 8,
                      [1] * 16 + [0] * 8], np.int32)
    args = (t["q"], t["k_hist"], t["v_hist"], t["k_cand"], t["v_cand"],
            bias, -0.5)
    got = fs_ops.hstu_cached_attention(*args, n_norm=N_NORM,
                                       row_index=seg, path="kernel")
    want = fs_ops.hstu_cached_attention(*args, n_norm=N_NORM,
                                        row_index=seg, path="jnp")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    oracle = fs_ref.pointwise_reference(*args[:6], n_norm=N_NORM,
                                        self_bias=-0.5, row_index=seg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(oracle))


# Packed rows against the kernel's wide q blocks (min(128, Mp) rows in
# sub-blocks of 8): a block whose sub-blocks share a pool row is scored
# whole, any other one sub-block at a time.
WIDE_LAYOUTS = {
    # one segment fills an Mp = 128 row: one uniform block
    "uniform": [[1] * 128],
    # three users' 8-aligned segments in one 64-row block: mixed
    "mixed": [[0] * 16 + [2] * 8 + [1] * 24],
    # Mp = 256: a 128-candidate segment (uniform), then small ones (mixed)
    "both": [[1] * 128 + [2] * 8 + [0] * 40 + [1] * 80],
    # Mp = 32 (a 32-row block): one row uniform, one mixed
    "short": [[2] * 32, [2] * 8 + [0] * 24],
}


@pytest.mark.parametrize("bk", [8, 16])
@pytest.mark.parametrize("store", ["native", "int8"])
@pytest.mark.parametrize("layout", list(WIDE_LAYOUTS))
def test_packed_wide_blocks_bitwise(layout, store, bk):
    """Every candidate of a uniform or mixed q block reads its own pool
    row's whole history (five blocks of 8, none padded, or three of 16, the
    last padded) and its own key, to the bit, against the jnp path and the
    oracle."""
    seg = np.asarray(WIDE_LAYOUTS[layout], np.int32)
    b, m = seg.shape
    t, rng = _exact_case(4, b=b, m=m, h=2, s=40, u=3, store=store)
    bias = _dyadic(rng, (3, 1, 40))
    args = (t["q"], t["k_hist"], t["v_hist"], t["k_cand"], t["v_cand"],
            bias, 0.25)
    kw = dict(n_norm=N_NORM, k_scale=t["k_scale"], v_scale=t["v_scale"],
              row_index=seg, bk=bk)
    got = fs_ops.hstu_cached_attention(*args, path="kernel", **kw)
    want = fs_ops.hstu_cached_attention(*args, path="jnp", **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    oracle = fs_ref.pointwise_reference(
        *args[:6], n_norm=N_NORM, self_bias=0.25, k_scale=t["k_scale"],
        v_scale=t["v_scale"], row_index=seg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(oracle))


@pytest.mark.parametrize("bk", [8, 128])
def test_extend_mask_bitwise(bk):
    """Suffix queries see the whole prefix (one bias row each) and the
    causal triangle of the suffix."""
    t, rng = _exact_case(3, b=2, m=20, h=2, s=30, u=2)
    args = (t["q"], t["k_hist"], t["v_hist"], t["k_cand"], t["v_cand"],
            _dyadic(rng, (2, 20, 30)), _dyadic(rng, (2, 20, 20)))
    got = fs_ops.hstu_extend_attention(*args, n_norm=N_NORM, path="kernel",
                                       bk=bk)
    want = fs_ops.hstu_extend_attention(*args, n_norm=N_NORM, path="jnp",
                                        bk=bk)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    oracle = fs_ref.pointwise_reference(*args[:6], n_norm=N_NORM,
                                        mode="extend", bias_cand=args[6])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(oracle))
    # the lane of a suffix key above the diagonal stays exactly zero
    out = np.asarray(got)
    assert (out[:, 0, :, 30 + 1:30 + 20] == 0).all()


def _random_case(seed, b, m, h, s, u, d=16):
    ks = jax.random.split(jax.random.key(seed), 6)
    return dict(
        q=jax.random.normal(ks[0], (b, m, h, d)),
        k_hist=jax.random.normal(ks[1], (u, s, h, d)),
        v_hist=jax.random.normal(ks[2], (u, s, h, d)),
        k_cand=jax.random.normal(ks[3], (b, m, h, d)),
        v_cand=jax.random.normal(ks[4], (b, m, h, d)),
        bias=jax.random.normal(ks[5], (u, 1, s)))


@pytest.mark.parametrize("path", ["jnp", "kernel"])
@pytest.mark.parametrize("store", ["native", "int8"])
def test_cached_random_operands_match_oracle(path, store):
    t = _random_case(5, b=3, m=12, h=2, s=37, u=2)
    ks = vs = None
    kh, vh = t["k_hist"], t["v_hist"]
    if store == "int8":
        qk, qv = quantize_leaf(kh, "int8"), quantize_leaf(vh, "int8")
        kh, vh, ks, vs = qk.q, qv.q, qk.scale, qv.scale
    # a 1-D dedup index, then a packed 2-D one (8-aligned segments)
    packed = np.asarray([[0] * 8 + [1] * 4, [1] * 12, [1] * 8 + [0] * 4],
                        np.int32)
    for idx in (jnp.asarray([1, 0, 1], jnp.int32), packed):
        got = fs_ops.hstu_cached_attention(
            t["q"], kh, vh, t["k_cand"], t["v_cand"], t["bias"], 0.3,
            n_norm=64.0, k_scale=ks, v_scale=vs, row_index=idx,
            path=path, bk=16)
        want = fs_ref.pointwise_reference(
            t["q"], kh, vh, t["k_cand"], t["v_cand"], t["bias"],
            n_norm=64.0, self_bias=0.3, k_scale=ks, v_scale=vs,
            row_index=idx)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=TOL, rtol=TOL)


@pytest.mark.parametrize("path", ["jnp", "kernel"])
def test_extend_random_operands_match_oracle(path):
    t = _random_case(6, b=2, m=12, h=2, s=37, u=2)
    ks = jax.random.split(jax.random.key(9), 2)
    bp = jax.random.normal(ks[0], (2, 12, 37))
    bs = jax.random.normal(ks[1], (2, 12, 12))
    got = fs_ops.hstu_extend_attention(
        t["q"], t["k_hist"], t["v_hist"], t["k_cand"], t["v_cand"], bp, bs,
        n_norm=64.0, path=path, bk=16)
    want = fs_ref.pointwise_reference(
        t["q"], t["k_hist"], t["v_hist"], t["k_cand"], t["v_cand"], bp,
        n_norm=64.0, mode="extend", bias_cand=bs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=TOL, rtol=TOL)


# ---------------------------------------------------------------------------
# 3. the relative bias: position Toeplitz + lane-gathered time table
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_q,n_k,offset", [(300, 300, 0), (24, 40, 4090),
                                            (5, 130, 0)])
def test_relative_bias_kernel_is_the_gather(n_q, n_k, offset):
    """Several q and k tiles; gaps from 0 to near the int32 range (every
    bucket an int32 gap reaches); position offsets clipped at both ends of
    the table: the Toeplitz windows and the kernel's lane gather read
    exactly the entries an XLA gather reads."""
    rng = np.random.default_rng(n_q + n_k)
    w_pos = jnp.asarray(rng.standard_normal(4097), jnp.bfloat16)
    w_time = jnp.asarray(rng.standard_normal(129), jnp.bfloat16)
    t_q = rng.integers(0, 2**31 - 1, (2, n_q)).astype(np.int32)
    t_k = rng.integers(0, 2**31 - 1, (2, n_k)).astype(np.int32)
    t_k[:, :3] = t_q[:, :1]                         # zero gaps: bucket 0
    got = fs_ops.hstu_relative_bias(w_pos, w_time, t_q, t_k, offset=offset,
                                    path="kernel")
    want = fs_ops.hstu_relative_bias(w_pos, w_time, t_q, t_k,
                                     offset=offset, path="jnp")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    wp, wt = np.asarray(w_pos, np.float32), np.asarray(w_time, np.float32)
    rel = np.clip(offset + np.arange(n_q)[:, None] - np.arange(n_k)[None],
                  0, 4096)
    gap = np.maximum(np.abs(t_q[:, :, None].astype(np.int64)
                            - t_k[:, None, :]), 1).astype(np.float32)
    bucket = np.minimum(np.floor(np.log(gap) / np.float32(0.301)), 128)
    assert bucket.max() <= 71                       # int32 gaps: < 72
    oracle = wp[rel][None] + wt[bucket.astype(np.int64)]
    np.testing.assert_allclose(np.asarray(got), oracle, atol=0, rtol=0)
