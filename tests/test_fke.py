"""FKE (fused candidate-scoring engine) parity suite.

Layers of coverage:

  1. oracle contract — ``kernels/fused_score/ref.py`` (dequantize → gather
     → concat → reference attention) is BITWISE identical to the framework
     reference path it replaces;
  2. op parity — the Pallas kernel (interpret mode) and the fused jnp fast
     path vs the oracle, swept over q_offset (history length), dedup
     row-index, int8/bf16 stored operands, and ragged (non-block-aligned)
     tails, for both cached-candidate and extend attention;
  3. model level — ``score_candidates`` / ``extend_history`` under
     ``impl="fused"`` vs the reference impl, including raw quantized pool
     views and row-index dispatch;
  4. serving level — the fused FlameEngine vs the full-pass engine across
     pool dtypes, dedup auto-enabled (and free) on the CPU backend, the
     default extension-bucket ladder + re-encode crossover policy, and the
     extension-refresh drift cap over a long stale-sweep session.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import climber as C
from repro.core import sumi
from repro.kernels.fused_score import ops as fs_ops
from repro.kernels.fused_score import ref as fs_ref
from repro.models import build_model
from repro.serving.kv_cache import (dequantize_kv, quantize_kv, quantize_leaf,
                                    raw_kv_specs, raw_kv_view)
from repro.types import ClimberConfig

TOL = 2e-5          # f32 operands: reassociated scale/softmax math
QTOL = 2e-2         # int8-quantized operands: quantization error dominates


def _mk(seed, b, m, h, hkv, d, s, u=None):
    ks = jax.random.split(jax.random.key(seed), 6)
    u = b if u is None else u
    return dict(
        q=jax.random.normal(ks[0], (b, m, h, d)),
        k_hist=jax.random.normal(ks[1], (u, s, hkv, d)),
        v_hist=jax.random.normal(ks[2], (u, s, hkv, d)),
        k_cand=jax.random.normal(ks[3], (b, m, hkv, d)),
        v_cand=jax.random.normal(ks[4], (b, m, hkv, d)),
    )


# ---------------------------------------------------------------------------
# 1. oracle contract
# ---------------------------------------------------------------------------

def test_oracle_bitwise_vs_framework_reference():
    """The fp32 oracle == the framework path (dequant + gather + concat +
    reference attention through core/sumi.py), bit for bit."""
    t = _mk(0, b=3, m=12, h=4, hkv=2, d=16, s=37, u=2)
    idx = jnp.array([1, 0, 1], jnp.int32)
    qk = quantize_leaf(t["k_hist"], "int8")
    qv = quantize_leaf(t["v_hist"], "int8")
    got = fs_ref.cached_reference(
        t["q"], qk.q, qv.q, t["k_cand"], t["v_cand"], k_scale=qk.scale,
        v_scale=qv.scale, row_index=idx, kv_dtype=jnp.float32)
    # framework path: sumi materializes dequant+gather then concat+reference
    exp = sumi.cached_candidate_attention(
        t["q"], qk.q, qv.q, t["k_cand"], t["v_cand"], impl="reference",
        k_scale=qk.scale, v_scale=qv.scale, row_index=idx)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))


def test_oracle_extend_bitwise_vs_framework_reference():
    t = _mk(1, b=2, m=9, h=2, hkv=2, d=16, s=25)
    got = fs_ref.extend_reference(t["q"], t["k_hist"], t["v_hist"],
                                  t["k_cand"], t["v_cand"])
    exp = sumi.extend_attention(t["q"], t["k_hist"], t["v_hist"],
                                t["k_cand"], t["v_cand"], impl="reference")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))


# ---------------------------------------------------------------------------
# 2. op parity sweeps (kernel + jnp fast path vs oracle)
# ---------------------------------------------------------------------------

CASES = [
    # b, m, h, hkv, d, s, u, idx?, dtype
    (2, 16, 4, 2, 32, 64, None, False, "native"),
    (3, 12, 4, 2, 16, 37, 2, True, "native"),       # ragged + dedup idx
    (2, 8, 2, 2, 16, 100, None, False, "int8"),
    (3, 20, 4, 1, 16, 51, 2, True, "int8"),         # gqa + ragged + idx
    (2, 16, 2, 2, 16, 33, None, False, "bf16"),
    (1, 5, 2, 2, 48, 7, None, False, "native"),     # tiny ragged tail
]


def _quant(t, dtype):
    if dtype == "native":
        return dict(t, k_scale=None, v_scale=None), TOL
    qk = quantize_leaf(t["k_hist"], dtype)
    qv = quantize_leaf(t["v_hist"], dtype)
    out = dict(t, k_hist=qk.q, v_hist=qv.q, k_scale=qk.scale,
               v_scale=qv.scale)
    return out, (QTOL if dtype == "int8" else TOL)


@pytest.mark.parametrize("case", CASES,
                         ids=[f"{c[8]}-s{c[5]}-m{c[1]}" + ("-idx" if c[7]
                              else "") for c in CASES])
@pytest.mark.parametrize("path", ["jnp", "kernel"])
def test_cached_op_parity(case, path):
    b, m, h, hkv, d, s, u, use_idx, dtype = case
    t = _mk(b * 131 + m * 17 + s, b, m, h, hkv, d, s, u)
    t, tol = _quant(t, dtype)
    idx = jnp.asarray(np.random.default_rng(0).integers(0, u or b, b),
                      jnp.int32) if use_idx else None
    ref = fs_ref.cached_reference(
        t["q"], t["k_hist"], t["v_hist"], t["k_cand"], t["v_cand"],
        k_scale=t["k_scale"], v_scale=t["v_scale"], row_index=idx,
        kv_dtype=jnp.float32)
    got = fs_ops.fused_cached_attention(
        t["q"], t["k_hist"], t["v_hist"], t["k_cand"], t["v_cand"],
        k_scale=t["k_scale"], v_scale=t["v_scale"], row_index=idx,
        path=path)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=tol, rtol=tol)


def test_cached_seg_gemm_long_history_parity(monkeypatch):
    """Segment-packed (2-D ``row_index``) scoring at ``S >=
    _SEG_GEMM_MIN_S`` takes the dense-GEMM + one-hot-selection form
    instead of gathering an M-times-replicated [B,M,S,Hkv,D] history.
    The selection is algebraically exact, so the two forms must agree to
    plain f32 reassociation tolerance even on int8 operands (both read
    the same stored values; only contraction order differs)."""
    s = fs_ops._SEG_GEMM_MIN_S + 33
    b, m, h, hkv, d, u = 2, 10, 4, 2, 16, 3
    t = _mk(9, b, m, h, hkv, d, s, u)
    t, _ = _quant(t, "int8")
    idx2 = jnp.asarray(np.random.default_rng(1).integers(0, u, (b, m)),
                       jnp.int32)

    def call():
        return fs_ops.fused_cached_attention(
            t["q"], t["k_hist"], t["v_hist"], t["k_cand"], t["v_cand"],
            k_scale=t["k_scale"], v_scale=t["v_scale"], row_index=idx2,
            path="jnp")

    got = call()                                     # dense-GEMM form
    fs_ops._fused_jnp.clear_cache()
    monkeypatch.setattr(fs_ops, "_SEG_GEMM_MIN_S", s + 1)
    exp = call()                                     # gathered form
    fs_ops._fused_jnp.clear_cache()                  # drop patched trace
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("case", CASES,
                         ids=[f"{c[8]}-s{c[5]}-m{c[1]}" + ("-idx" if c[7]
                              else "") for c in CASES])
@pytest.mark.parametrize("path", ["jnp", "kernel"])
def test_extend_op_parity(case, path):
    """Extend (causal suffix vs cached prefix) over the same operand sweep
    — b rows, m suffix tokens, s prefix positions."""
    b, m, h, hkv, d, s, u, use_idx, dtype = case
    t = _mk(b * 131 + m * 17 + s + 7, b, m, h, hkv, d, s, u)
    t, tol = _quant(t, dtype)
    idx = jnp.asarray(np.random.default_rng(1).integers(0, u or b, b),
                      jnp.int32) if use_idx else None
    ref = fs_ref.extend_reference(
        t["q"], t["k_hist"], t["v_hist"], t["k_cand"], t["v_cand"],
        k_scale=t["k_scale"], v_scale=t["v_scale"], row_index=idx,
        kv_dtype=jnp.float32)
    got = fs_ops.fused_extend_attention(
        t["q"], t["k_hist"], t["v_hist"], t["k_cand"], t["v_cand"],
        k_scale=t["k_scale"], v_scale=t["v_scale"], row_index=idx,
        path=path)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=tol, rtol=tol)


def test_cached_q_offset_sweep():
    """Cached scoring is exact for any history length (the q_offset the
    candidates sit at), including block-straddling offsets."""
    for s in (1, 8, 63, 64, 65, 130):
        t = _mk(s, b=1, m=10, h=2, hkv=2, d=16, s=s)
        ref = fs_ref.cached_reference(t["q"], t["k_hist"], t["v_hist"],
                                      t["k_cand"], t["v_cand"])
        for path in ("jnp", "kernel"):
            got = fs_ops.fused_cached_attention(
                t["q"], t["k_hist"], t["v_hist"], t["k_cand"], t["v_cand"],
                path=path)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       atol=TOL, rtol=TOL, err_msg=f"s={s}")


def test_fused_attention_dispatch_split():
    """models/attention.py impl="fused" on a pre-concatenated sumi call
    splits the KV axis and matches the reference dispatch."""
    from repro.models import attention as A
    t = _mk(9, b=2, m=8, h=4, hkv=2, d=16, s=40)
    k = jnp.concatenate([t["k_hist"], t["k_cand"]], axis=1)
    v = jnp.concatenate([t["v_hist"], t["v_cand"]], axis=1)
    ref = A.attention(t["q"], k, v, "sumi", impl="reference",
                      n_history=40, q_offset=40)
    got = A.attention(t["q"], k, v, "sumi", impl="fused",
                      n_history=40, q_offset=40)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=TOL, rtol=TOL)


# ---------------------------------------------------------------------------
# 3. model level
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def climber_setup():
    cfg = dataclasses.replace(
        get_config("climber"), vocab_size=5_000, d_model=64, d_ff=128,
        n_heads=2, n_kv_heads=2, head_dim=32,
        climber=ClimberConfig(num_blocks=2, layers_per_block=2))
    bundle = build_model(cfg)
    params, _ = bundle.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    batch = {"history": jnp.asarray(rng.integers(0, 5000, (1, 64)),
                                    jnp.int32),
             "candidates": jnp.asarray(rng.integers(0, 5000, (1, 12)),
                                       jnp.int32),
             "side": jnp.asarray(rng.normal(size=(1, 12)), jnp.float32)}
    return cfg, bundle, params, batch


def test_score_candidates_fused_parity(climber_setup):
    cfg, bundle, params, batch = climber_setup
    full = C.climber_forward(params, batch, cfg, impl="reference")
    kv = C.encode_history(params, batch, cfg, impl="reference")
    got = C.score_candidates(params, kv, batch["candidates"], cfg,
                             impl="fused")
    np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                               atol=TOL, rtol=TOL)


def test_score_candidates_raw_quantized_views(climber_setup):
    """Raw int8 pool views + row_index through the fused impl track the
    dequantized framework path at the quantization tolerance, and the raw
    spec pytree matches the raw view structure."""
    cfg, bundle, params, batch = climber_setup
    kv = C.encode_history(params, batch, cfg, impl="reference")
    ref = C.score_candidates(params, dequantize_kv(quantize_kv(kv, "int8")[0]),
                             batch["candidates"], cfg, impl="reference")
    raw = raw_kv_view(quantize_kv(kv, "int8")[0])
    specs = raw_kv_specs(jax.eval_shape(lambda x: x, kv), "int8")
    assert jax.tree.structure(raw) == jax.tree.structure(specs)
    for leaf, spec in zip(jax.tree.leaves(raw), jax.tree.leaves(specs)):
        assert leaf.shape == spec.shape and leaf.dtype == spec.dtype
    got = C.score_candidates(params, raw, batch["candidates"], cfg,
                             impl="fused",
                             row_index=jnp.zeros((1,), jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=QTOL, rtol=QTOL)


def test_extend_history_fused_parity(climber_setup):
    cfg, bundle, params, batch = climber_setup
    kv = C.encode_history(params, batch, cfg, impl="reference")
    for prefix in (0, 17, 40, 64):
        got = C.extend_history(params, kv, batch, cfg, prefix_len=prefix,
                               impl="fused")
        exp = C.extend_history(params, kv, batch, cfg, prefix_len=prefix,
                               impl="reference")
        for b in got:
            for kk in ("k", "v"):
                np.testing.assert_allclose(
                    np.asarray(got[b][kk]), np.asarray(exp[b][kk]),
                    atol=TOL, rtol=TOL, err_msg=f"prefix={prefix}")


# ---------------------------------------------------------------------------
# 4. serving level
# ---------------------------------------------------------------------------

def _engine(bundle, params, **kw):
    from repro.core.pda import RemoteFeatureStore
    from repro.serving import FlameEngine
    base = dict(n_history=64, buckets=(16, 8), n_streams=2,
                feature_mode="sync",
                store=RemoteFeatureStore(latency_s=0.0, feature_dim=12),
                window_s=0.004, max_batch=2, n_workers=2)
    base.update(kw)
    return FlameEngine(bundle, params, **base)


@pytest.mark.parametrize("pool_dtype", ["native", "int8", "bf16"])
def test_engine_fused_parity_and_free_dedup(climber_setup, pool_dtype):
    """The fused engine matches the full-pass engine at the pool tolerance,
    keeps hit/miss responses bitwise-stable (one shared quantized
    representation), and — because the row gather is folded into the
    kernel — auto-enables KV-row dedup even on the CPU backend."""
    cfg, bundle, params, _ = climber_setup
    rng = np.random.default_rng(3)
    hist = rng.integers(0, 5000, 80).astype(np.int32)
    cand = rng.integers(0, 5000, 32).astype(np.int32)    # 2x bucket-16 chunks
    eng = _engine(bundle, params, history_cache=True, pool_slots=4,
                  pool_dtype=pool_dtype, impl="fused")
    full = _engine(bundle, params)
    try:
        assert eng._kv_dedup, "fused impl must auto-enable kv_dedup on CPU"
        a = eng.serve(hist, cand, user_id=1)             # miss
        b = eng.serve(hist, cand, user_id=1)             # hit
        m = eng.metrics()
        assert m["dso_dedup_rows_saved"] >= 1
        np.testing.assert_array_equal(a, b)
        ref = full.serve(hist, cand)
        tol = QTOL if pool_dtype == "int8" else 2e-3
        np.testing.assert_allclose(a.astype(np.float32),
                                   ref.astype(np.float32),
                                   atol=tol, rtol=tol)
    finally:
        eng.shutdown()
        full.shutdown()


def test_engine_fused_matches_chunked_on_int8_pool(climber_setup):
    """Over int8 pools the fused engine scores a repeat-user session as the
    chunked engine does, misses and hits alike: chunked dequantizes the
    stored rows, fused folds the scales into its score and accumulator
    multiplies, so only the order of the math separates them."""
    cfg, bundle, params, _ = climber_setup
    rng = np.random.default_rng(11)
    hists = [rng.integers(0, 5000, 80).astype(np.int32) for _ in range(3)]
    session = [(u, rng.integers(0, 5000, m).astype(np.int32))
               for u, m in ((0, 24), (1, 9), (0, 32), (2, 16), (1, 24),
                            (2, 5))]
    outs = []
    for impl in ("chunked", "fused"):
        eng = _engine(bundle, params, history_cache=True, pool_slots=4,
                      pool_dtype="int8", impl=impl)
        try:
            outs.append([eng.serve(hists[u], c, user_id=u)
                         for u, c in session])
        finally:
            eng.shutdown()
    for a, b in zip(*outs):
        np.testing.assert_allclose(a.astype(np.float32),
                                   b.astype(np.float32), atol=1e-2, rtol=0)


def test_engine_default_extension_ladder(climber_setup):
    """incremental_history without explicit buckets ships the (n, 3n/4,
    n/2) trusted-prefix ladder."""
    cfg, bundle, params, _ = climber_setup
    eng = _engine(bundle, params, history_cache=True, pool_slots=4,
                  incremental_history=True)
    try:
        assert eng.dso.families["extend"] == [64, 48, 32]
    finally:
        eng.shutdown()


def test_engine_extension_crossover_reencodes(climber_setup):
    """A stale hit whose shared prefix only fits a rung below half the
    window re-encodes in full (re-encode-vs-extend crossover) instead of
    extending almost the whole window."""
    cfg, bundle, params, _ = climber_setup
    eng = _engine(bundle, params, history_cache=True, pool_slots=4,
                  incremental_history=True, extend_buckets=(64, 16))
    rng = np.random.default_rng(5)
    h1 = rng.integers(0, 5000, 64).astype(np.int32)
    h2 = h1.copy()
    h2[20:] = rng.integers(0, 5000, 44)                  # shared prefix 20
    cand = rng.integers(0, 5000, 8).astype(np.int32)
    try:
        eng.serve(h1, cand, user_id=2)
        eng.serve(h2, cand, user_id=2)                   # bucket 16 < 32 cap
        m = eng.metrics()
        assert m["pool_extensions"] == 0
        assert m["dso_dispatches_encode"] == 2
    finally:
        eng.shutdown()


def test_engine_refresh_limit_bounds_drift(climber_setup):
    """Long stale-sweep session under an int8 pool: every sweep
    tail-appends, so every request is an extendable stale hit that
    re-quantizes the basis.  With --extend-refresh-limit the engine forces
    a full re-encode every K extensions; drift vs a fresh-encode engine
    stays bounded for the whole session and the forced re-encodes are
    visible in the metrics."""
    cfg, bundle, params, _ = climber_setup
    K = 3
    eng = _engine(bundle, params, history_cache=True, pool_slots=4,
                  pool_dtype="int8", incremental_history=True,
                  extend_refresh_limit=K, impl="fused")
    fresh = _engine(bundle, params, history_cache=True, pool_slots=4)
    rng = np.random.default_rng(7)
    hist = rng.integers(0, 5000, 80).astype(np.int32)
    cand = rng.integers(0, 5000, 8).astype(np.int32)
    n_sweeps = 2 * K + 2
    try:
        eng.serve(hist, cand, user_id=1)                 # cold encode
        drift = []
        for _ in range(n_sweeps):
            hist = np.concatenate(
                [hist, rng.integers(0, 5000, 4).astype(np.int32)])
            out = eng.serve(hist, cand, user_id=1)
            ref = fresh.serve(hist, cand)                # content-hash keyed
            drift.append(float(np.abs(out.astype(np.float32)
                                      - ref.astype(np.float32)).max()))
        m = eng.metrics()
        assert m["pool_refresh_reencodes"] >= 2, m
        assert m["pool_extensions"] >= K, m
        assert max(drift) < QTOL, drift
    finally:
        eng.shutdown()
        fresh.shutdown()
