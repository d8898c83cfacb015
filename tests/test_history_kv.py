"""History-KV reuse: split SUMI forward + HistoryKVPool + cache-aware engine.

Covers the three layers of the refactor:
  1. the candidate-vs-cached-KV attention path (``q_offset``) against the
     monolithic SUMI pass, for all three impls;
  2. climber's ``encode_history`` / ``score_candidates`` decomposition
     against ``climber_forward``;
  3. the serving stack — HistoryKVPool LRU semantics (propcheck), concurrent
     hit/miss accounting, and FlameEngine's cache-aware execution path.
"""
import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import climber as C
from repro.core import sumi
from repro.models import attention as A
from repro.models import build_model
from repro.serving import FlameEngine, HistoryKVPool
from repro.serving.kv_cache import HistoryKVPool as _PoolAlias
from repro.types import ClimberConfig
from tests._propcheck import given, settings, st

assert HistoryKVPool is _PoolAlias


# ---------------------------------------------------------------------------
# 1. attention substrate: q_offset candidate path vs monolithic SUMI
# ---------------------------------------------------------------------------

def _qkv(key, b, s, h, hkv, d):
    ks = jax.random.split(jax.random.key(key), 3)
    return (jax.random.normal(ks[0], (b, s, h, d), jnp.float32),
            jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32),
            jax.random.normal(ks[2], (b, s, hkv, d), jnp.float32))


@pytest.mark.parametrize("nh,m,h,hkv,d", [
    (150, 30, 4, 2, 32),     # GQA, non-aligned history
    (33, 9, 2, 2, 16),       # history tail shares a block with candidates
    (64, 64, 2, 1, 64),      # block-aligned history, many candidates
])
def test_q_offset_paths_match_monolithic(nh, m, h, hkv, d):
    q, k, v = _qkv(nh + m, 2, nh + m, h, hkv, d)
    full = A.reference_attention(q, k, v, "sumi", n_history=nh)[:, nh:]
    qc = q[:, nh:]
    ref = A.reference_attention(qc, k, v, "sumi", n_history=nh, q_offset=nh)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(full))
    ch = A.chunked_attention(qc, k, v, "sumi", n_history=nh,
                             q_chunk=16, k_chunk=16, q_offset=nh)
    np.testing.assert_allclose(np.asarray(ch), np.asarray(full),
                               atol=2e-5, rtol=2e-5)
    from repro.kernels.flash_attention import ops as fa_ops
    pl = fa_ops.flash_attention(qc, k, v, "sumi", n_history=nh,
                                q_offset=nh, interpret=True)
    np.testing.assert_allclose(np.asarray(pl), np.asarray(full),
                               atol=2e-5, rtol=2e-5)


def test_cached_candidate_attention_helper():
    nh, m = 40, 12
    q, k, v = _qkv(7, 2, nh + m, 4, 4, 32)
    tau = 1.3
    # jit-wrapped and compared against the candidate rows of the monolithic
    # SUMI pass computed at the same query count (q_offset): XLA's CPU dots
    # may round differently when only the query count changes, and
    # test_q_offset_paths_match_monolithic links those rows to the full pass
    full = jax.jit(lambda q, k, v: A.reference_attention(
        q[:, nh:] / jnp.asarray(tau, q.dtype), k, v, "sumi", n_history=nh,
        q_offset=nh))(q, k, v)
    out = jax.jit(lambda q, k, v: sumi.cached_candidate_attention(
        q[:, nh:], k[:, :nh], v[:, :nh], k[:, nh:], v[:, nh:],
        impl="reference", temperature=tau))(q, k, v)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(full))


# ---------------------------------------------------------------------------
# 2. climber decomposition: encode_history + score_candidates == forward
# ---------------------------------------------------------------------------

def _climber_cfg():
    return dataclasses.replace(
        get_config("climber"), vocab_size=3000, d_model=128, d_ff=256,
        n_heads=4, n_kv_heads=4, head_dim=32,
        climber=ClimberConfig(num_blocks=2, layers_per_block=2))


@pytest.fixture(scope="module")
def climber():
    cfg = _climber_cfg()
    params, _ = C.climber_init(jax.random.key(0), cfg)
    ks = jax.random.split(jax.random.key(1), 3)
    batch = {"history": jax.random.randint(ks[0], (2, 64), 0, 3000),
             "candidates": jax.random.randint(ks[1], (2, 16), 0, 3000),
             "side": jax.random.normal(ks[2], (2, 12))}
    return cfg, params, batch


@pytest.mark.parametrize("impl", ["reference", "chunked", "pallas"])
def test_encode_score_matches_monolithic(climber, impl):
    """The acceptance gate: cached-history candidate scores are numerically
    identical to the monolithic SUMI forward — bitwise where the impl keeps
    the same reduction order (reference; chunked routes there at this
    scale), allclose at bf16-tight tolerance for the block-reordered pallas
    interpret path."""
    cfg, params, batch = climber
    full = jax.jit(lambda p, b: C.climber_forward(p, b, cfg, impl=impl))(
        params, batch)
    kv = jax.jit(lambda p, b: C.encode_history(p, b, cfg, impl=impl))(
        params, batch)
    got = jax.jit(lambda p, kv, c: C.score_candidates(
        p, kv, c, cfg, impl=impl))(params, kv, batch["candidates"])
    if impl == "pallas":
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(full, np.float32),
                                   atol=5e-3, rtol=5e-3)
    else:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(full))


def test_bundle_split_surface_matches_prefill(climber):
    cfg, params, batch = climber
    bundle = build_model(cfg)
    probs = jax.jit(lambda p, b: bundle.prefill(p, b, impl="reference"))(
        params, batch)
    kv = jax.jit(lambda p, b: bundle.encode_history(
        p, b, impl="reference"))(params, batch)
    got = jax.jit(lambda p, kv, c: bundle.score_candidates(
        p, kv, c, impl="reference"))(params, kv, batch["candidates"])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(probs))


def test_history_kv_specs_match_encode(climber):
    cfg, params, batch = climber
    bundle = build_model(cfg)
    specs = bundle.history_kv_specs(params, 64, batch=2)
    kv = bundle.encode_history(params, batch)
    got = jax.tree.map(lambda a: (a.shape, a.dtype), kv)
    want = jax.tree.map(lambda s: (s.shape, s.dtype), specs)
    assert got == want
    # leading axis is batch (so serving can stack pool rows along axis 0)
    assert specs["b0"]["k"].shape[0] == 2


def test_kv_independent_of_candidates(climber):
    """The refactor's premise: history K/V must not depend on the candidate
    set (SUMI keeps the prefix self-contained)."""
    cfg, params, batch = climber
    # K/V encoded once (no candidates in sight) score a DIFFERENT slate
    # bitwise like the monolithic pass over that slate; both programs are
    # jitted at one slate size, as the engine's bucketed executors are
    kv1 = jax.jit(lambda p, b: C.encode_history(p, b, cfg))(params, batch)
    other = jax.random.randint(jax.random.key(2),
                               batch["candidates"].shape, 0, 3000)
    b2 = dict(batch, candidates=other)
    full2 = jax.jit(lambda p, b: C.climber_forward(p, b, cfg))(params, b2)
    got = jax.jit(lambda p, kv, c: C.score_candidates(p, kv, c, cfg))(
        params, kv1, b2["candidates"])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(full2))


# ---------------------------------------------------------------------------
# 3a. HistoryKVPool semantics
# ---------------------------------------------------------------------------

def _kv(i, n=64):
    return {"k": np.full((1, 2, 4), i, np.float32),
            "v": np.full((1, 2, 4), i, np.float32)}


def test_pool_hit_miss_and_bytes():
    p = HistoryKVPool(slots=4)
    assert p.get("u1", "f1") is None                   # cold miss
    p.put("u1", "f1", _kv(1))
    got = p.get("u1", "f1")
    np.testing.assert_array_equal(got["k"], _kv(1)["k"])
    s = p.stats()
    assert s["hits"] == 1 and s["misses"] == 1 and s["entries"] == 1
    assert s["bytes"] == 2 * 8 * 4                      # two [1,2,4] f32


def test_pool_stale_fingerprint_is_miss():
    p = HistoryKVPool(slots=4)
    p.put("u1", "f1", _kv(1))
    assert p.get("u1", "f2") is None                    # history advanced
    s = p.stats()
    assert s["stale"] == 1 and s["misses"] == 1 and s["entries"] == 0
    p.put("u1", "f2", _kv(2))
    assert p.get("u1", "f2")["k"][0, 0, 0] == 2


def test_pool_lru_eviction_order():
    p = HistoryKVPool(slots=3)
    for i in range(3):
        p.put(f"u{i}", "f", _kv(i))
    p.get("u0", "f")                                    # refresh u0
    p.put("u3", "f", _kv(3))                            # evicts u1 (LRU)
    assert p.get("u1", "f") is None
    assert p.get("u0", "f") is not None
    assert p.stats()["evictions"] == 1
    assert len(p) == 3


def test_pool_release_on_shutdown():
    p = HistoryKVPool(slots=2)
    p.put("a", "f", _kv(0))
    p.put("b", "f", _kv(1))
    p.release()
    assert len(p) == 0 and p.stats()["bytes"] == 0
    assert p.get("a", "f") is None                      # counters survive
    assert p.stats()["misses"] == 1


@given(st.lists(st.tuples(st.integers(0, 7), st.integers(0, 1)),
                min_size=1, max_size=40),
       st.integers(1, 5))
@settings(max_examples=40, deadline=None)
def test_pool_lru_eviction_property(ops, slots):
    """Model check: after any put/get sequence the pool holds exactly the
    ``slots`` most-recently-used non-stale keys, in LRU->MRU order."""
    p = HistoryKVPool(slots=slots)
    model = {}                       # key -> fingerprint, insertion=recency
    for key, is_put in ops:
        k = f"u{key}"
        if is_put:
            p.put(k, "f", _kv(key))
            model.pop(k, None)
            model[k] = "f"
            while len(model) > slots:
                del model[next(iter(model))]
        else:
            got = p.get(k, "f")
            assert (got is not None) == (k in model)
            if k in model:           # refresh recency
                model[k] = model.pop(k)
    assert p.keys() == list(model)


def test_pool_concurrent_counters_consistent():
    """Hit/miss accounting under concurrent submits: every get is counted
    exactly once and entries never exceed the slot budget."""
    p = HistoryKVPool(slots=4)
    n_threads, n_ops = 8, 200
    barrier = threading.Barrier(n_threads)

    def worker(tid):
        rng = np.random.default_rng(tid)
        barrier.wait()
        for _ in range(n_ops):
            key = f"u{rng.integers(8)}"
            if p.get(key, "f") is None:
                p.put(key, "f", _kv(0))

    ths = [threading.Thread(target=worker, args=(t,))
           for t in range(n_threads)]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    s = p.stats()
    assert s["hits"] + s["misses"] == n_threads * n_ops
    assert s["entries"] <= 4
    assert s["bytes"] == s["entries"] * 2 * 8 * 4


# ---------------------------------------------------------------------------
# 3b. cache-aware FlameEngine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def serving_setup():
    cfg = dataclasses.replace(
        get_config("climber"), vocab_size=5_000, d_model=64, d_ff=128,
        n_heads=2, n_kv_heads=2, head_dim=32,
        climber=ClimberConfig(num_blocks=2, layers_per_block=2))
    bundle = build_model(cfg)
    params, _ = bundle.init(jax.random.key(0))
    return cfg, bundle, params


def _engines(bundle, params, **kw):
    from repro.core.pda import RemoteFeatureStore
    base = dict(n_history=64, buckets=(16, 8), n_streams=2,
                feature_mode="sync",
                store=RemoteFeatureStore(latency_s=0.0, feature_dim=12),
                window_s=0.004, max_batch=2, n_workers=2)
    base.update(kw)
    return FlameEngine(bundle, params, **base)


def test_engine_cached_scores_match_full(serving_setup):
    cfg, bundle, params = serving_setup
    eng_full = _engines(bundle, params)
    eng_pool = _engines(bundle, params, history_cache=True, pool_slots=4)
    rng = np.random.default_rng(0)
    hist = rng.integers(0, 5000, 64).astype(np.int32)
    try:
        for m in (8, 12, 24):        # aligned, padded, multi-chunk
            cand = rng.integers(0, 5000, m).astype(np.int32)
            a = eng_full.serve(hist, cand)
            b = eng_pool.serve(hist, cand, user_id=1)
            assert a.shape == b.shape == (m, cfg.climber.num_tasks)
            np.testing.assert_allclose(a.astype(np.float32),
                                       b.astype(np.float32),
                                       atol=2e-3, rtol=2e-3)
        m = eng_pool.metrics()
        assert m["pool_hits"] == 2 and m["pool_misses"] == 1
        assert m["dso_dispatches_encode"] == 1
        assert m["pool_bytes"] > 0
    finally:
        eng_full.shutdown()
        eng_pool.shutdown()


def test_engine_hit_path_bitwise_vs_miss_path(serving_setup):
    """Hit and miss both score through the SAME cached executors, so scores
    for identical requests must be bitwise equal across the pool states."""
    cfg, bundle, params = serving_setup
    eng = _engines(bundle, params, history_cache=True, pool_slots=4)
    rng = np.random.default_rng(1)
    hist = rng.integers(0, 5000, 64).astype(np.int32)
    cand = rng.integers(0, 5000, 12).astype(np.int32)
    try:
        miss = eng.serve(hist, cand, user_id=9)         # encodes
        hit = eng.serve(hist, cand, user_id=9)          # pool hit
        np.testing.assert_array_equal(miss, hit)
    finally:
        eng.shutdown()


def test_engine_stale_history_reencodes(serving_setup):
    """Same user, changed history -> the pooled KV is stale; the engine must
    re-encode rather than score against outdated state."""
    cfg, bundle, params = serving_setup
    eng = _engines(bundle, params, history_cache=True, pool_slots=4)
    rng = np.random.default_rng(2)
    h1 = rng.integers(0, 5000, 64).astype(np.int32)
    h2 = rng.integers(0, 5000, 64).astype(np.int32)
    cand = rng.integers(0, 5000, 8).astype(np.int32)
    try:
        eng.serve(h1, cand, user_id=3)
        out2 = eng.serve(h2, cand, user_id=3)           # stale -> re-encode
        m = eng.metrics()
        assert m["pool_stale"] == 1 and m["pool_misses"] == 2
        # scores reflect the NEW history, not the stale KV
        eng2 = _engines(bundle, params, history_cache=True, pool_slots=4)
        try:
            fresh = eng2.serve(h2, cand, user_id=99)
            np.testing.assert_array_equal(out2, fresh)
        finally:
            eng2.shutdown()
    finally:
        eng.shutdown()


def test_engine_tail_only_history_change_is_stale(serving_setup):
    """The model truncates history to n_history but side features average
    the FULL array — a tail-only change must invalidate the pooled KV, and
    the pooled scores must track what the full-pass engine would serve."""
    cfg, bundle, params = serving_setup
    eng = _engines(bundle, params, history_cache=True, pool_slots=4)
    eng_full = _engines(bundle, params)
    rng = np.random.default_rng(5)
    h1 = rng.integers(0, 5000, 80).astype(np.int32)     # > n_history=64
    h2 = h1.copy()
    h2[70:] = rng.integers(0, 5000, 10)                 # tail-only change
    cand = rng.integers(0, 5000, 8).astype(np.int32)
    try:
        eng.serve(h1, cand, user_id=5)
        out2 = eng.serve(h2, cand, user_id=5)           # must re-encode
        assert eng.metrics()["pool_stale"] == 1
        np.testing.assert_allclose(
            out2.astype(np.float32),
            eng_full.serve(h2, cand).astype(np.float32),
            atol=2e-3, rtol=2e-3)
    finally:
        eng.shutdown()
        eng_full.shutdown()


def test_engine_pad_sentinel_does_not_leak(serving_setup):
    """m=5 into bucket 8 pads with the -1 sentinel; scores must equal an
    unpadded request for the same leading candidates, and negative real
    candidate ids are rejected up front."""
    cfg, bundle, params = serving_setup
    eng = _engines(bundle, params)
    rng = np.random.default_rng(3)
    hist = rng.integers(0, 5000, 64).astype(np.int32)
    cand8 = rng.integers(0, 5000, 8).astype(np.int32)
    try:
        full = eng.serve(hist, cand8)
        part = eng.serve(hist, cand8[:5])               # padded to bucket 8
        np.testing.assert_array_equal(part, full[:5])
        bad = cand8.copy()
        bad[2] = -1
        with pytest.raises(Exception, match="candidate ids must be >= 0"):
            eng.serve(hist, bad)
    finally:
        eng.shutdown()


def test_engine_concurrent_repeat_users(serving_setup):
    """Concurrent submits from a small user population: counters stay
    consistent and every response matches the full-pass engine."""
    from repro.serving import ServeRequest
    cfg, bundle, params = serving_setup
    eng = _engines(bundle, params, history_cache=True, pool_slots=8,
                   n_workers=4)
    rng = np.random.default_rng(4)
    users = {u: rng.integers(0, 5000, 64).astype(np.int32) for u in range(3)}
    reqs = [(u, rng.integers(0, 5000, 8).astype(np.int32))
            for u in list(users) * 6]
    try:
        futs = [eng.submit(ServeRequest(history=users[u], candidates=c,
                                        user_id=u)) for u, c in reqs]
        outs = [f.result().output for f in futs]
        m = eng.metrics()
        assert m["pool_hits"] + m["pool_misses"] == len(reqs)
        assert m["pool_misses"] >= len(users)
        assert len(eng.history_pool) == len(users)
        # single-flight: concurrent same-user misses share ONE encode
        assert m["dso_chunks_encode"] == len(users)
        # sequential re-serve of the same requests must be bitwise stable
        for (u, c), out in zip(reqs, outs):
            np.testing.assert_array_equal(
                eng.serve(users[u], c, user_id=u), out)
    finally:
        eng.shutdown()
