"""PDA v2: byte-budgeted / quantized / device-resident history-KV pool +
incremental suffix extension.

Four layers of coverage:
  1. quantization hooks — int8/bf16 round-trip error and stored-byte bounds;
  2. HistoryKVPool v2 — byte-budget LRU model check (never exceeds budget,
     evicts strictly LRU, rejects oversized), host-tier spill/reload
     identity;
  3. the incremental-extension substrate — causal ``q_offset`` attention
     parity (chunked + pallas vs reference) and ``extend_history`` bitwise
     vs a full re-encode for arbitrary shared-prefix lengths;
  4. the serving stack — FlameEngine extension on tail-append staleness,
     KV-row dedup for multi-chunk requests, int8 score-drift bound, and
     byte-budget accounting through ServeMetrics.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import climber as C
from repro.models import attention as A
from repro.models import build_model
from repro.serving.kv_cache import (HistoryKVPool, dequantize_kv,
                                    payload_bytes, quantize_kv,
                                    quantized_nbytes)
from repro.types import ClimberConfig
from tests._propcheck import given, settings, st

# int8 pool entries must stay inside this score drift vs a native pool
# (sigmoid outputs; measured ~2e-3 on the test config — the bound leaves
# an order of magnitude of headroom and fails loudly if quantization
# quality regresses)
INT8_SCORE_DRIFT_BOUND = 2e-2


# ---------------------------------------------------------------------------
# 1. quantization hooks
# ---------------------------------------------------------------------------

def _kv_tree(seed=0, shape=(1, 2, 16, 4, 8)):
    rng = np.random.default_rng(seed)
    return {"k": rng.normal(size=shape).astype(np.float32) * 3.0,
            "v": rng.normal(size=shape).astype(np.float32)}


def test_int8_round_trip_error_and_bytes():
    x = _kv_tree()
    pay, nbytes = quantize_kv(x, "int8")
    back = dequantize_kv(pay)
    for k in x:
        a, b = x[k], np.asarray(back[k])
        # per-(layer, head) absmax scaling: elementwise error <= scale/254
        scale = np.max(np.abs(a), axis=(2, 4), keepdims=True)
        assert np.all(np.abs(a - b) <= scale / 254 + 1e-7)
    raw = sum(a.size * 4 for a in x.values())
    assert nbytes < raw * 0.3           # ~4x capacity per byte budget


def test_bf16_round_trip_preserves_dtype():
    x = _kv_tree(1)
    pay, nbytes = quantize_kv(x, "bf16")
    back = dequantize_kv(pay)
    for k in x:
        assert np.asarray(back[k]).dtype == np.float32   # original dtype back
        assert np.abs(np.asarray(back[k]) - x[k]).max() <= \
            np.abs(x[k]).max() * 2 ** -8
    raw = sum(a.size * 4 for a in x.values())
    assert nbytes == raw // 2


def test_quantized_nbytes_matches_actual_payload():
    """The free admission precheck must agree exactly with the bytes the
    real quantization produces (budget decisions ride on it)."""
    x = _kv_tree(3)
    for dt in ("native", "bf16", "int8"):
        _, actual = quantize_kv(x, dt)
        assert quantized_nbytes(x, dt) == actual, dt


def test_native_passthrough_is_lossless():
    x = _kv_tree(2)
    pay, nbytes = quantize_kv(x, "native")
    back = dequantize_kv(pay)
    for k in x:
        np.testing.assert_array_equal(np.asarray(back[k]), x[k])
    assert nbytes == payload_bytes(pay) == sum(a.size * 4 for a in x.values())


# ---------------------------------------------------------------------------
# 2. pool v2: byte budget + spill tier
# ---------------------------------------------------------------------------

def _sized_kv(i, rows):
    return {"k": np.full((1, rows, 4), float(i), np.float32)}


_ROW_BYTES = 4 * 4      # one row of a _sized_kv leaf


@given(st.lists(st.tuples(st.integers(0, 5), st.integers(1, 6)),
                min_size=1, max_size=40),
       st.integers(4, 20))
@settings(max_examples=40, deadline=None)
def test_pool_byte_budget_lru_property(ops, budget_rows):
    """Model check: after any put sequence the pool holds the longest
    MRU-suffix of admitted entries that fits the byte budget, bytes_used
    never exceeds the budget, and oversized entries are rejected."""
    budget = budget_rows * _ROW_BYTES
    p = HistoryKVPool(slots=None, budget_bytes=budget)
    model = {}                        # key -> nbytes, insertion order = LRU
    for key, rows in ops:
        k = f"u{key}"
        nbytes = rows * _ROW_BYTES
        admitted = p.put(k, "f", _sized_kv(key, rows))
        if nbytes > budget:
            assert not admitted
        else:
            assert admitted
            model.pop(k, None)
            model[k] = nbytes
            while sum(model.values()) > budget:
                del model[next(iter(model))]          # strict LRU
        st_ = p.stats()
        assert st_["bytes"] <= budget
        assert p.keys() == list(model)
        assert st_["bytes"] == sum(model.values())


def test_pool_budget_and_slots_combine():
    p = HistoryKVPool(slots=2, budget_bytes=100 * _ROW_BYTES)
    for i in range(4):
        p.put(f"u{i}", "f", _sized_kv(i, 1))
    assert len(p) == 2 and p.keys() == ["u2", "u3"]   # slot bound still binds


def test_pool_spill_reload_identity():
    """An entry demoted to the host tier and promoted back must reload
    bitwise-identically (device -> host -> device round trip)."""
    ent = payload_bytes(quantize_kv(_sized_kv(0, 8), "native")[0])
    p = HistoryKVPool(slots=1, spill_bytes=8 * ent)
    kv0 = _kv_tree(7, shape=(1, 2, 8, 2, 4))
    p.put("a", "fa", kv0)
    p.put("b", "fb", _kv_tree(8, shape=(1, 2, 8, 2, 4)))   # a -> spill tier
    s = p.stats()
    assert s["spill_entries"] == 1 and s["spill_bytes"] > 0
    got = p.get("a", "fa")                                  # promote
    for k in kv0:
        np.testing.assert_array_equal(np.asarray(got[k]), kv0[k])
    s = p.stats()
    assert s["spill_hits"] == 1 and s["hits"] == 1
    # promotion re-admits under the slot bound: b was demoted in turn
    assert p.keys() == ["a"] and s["spill_entries"] == 1


def test_pool_spill_respects_budget():
    ent = payload_bytes(quantize_kv(_sized_kv(0, 4), "native")[0])
    p = HistoryKVPool(slots=1, spill_bytes=2 * ent)
    for i in range(5):
        p.put(f"u{i}", "f", _sized_kv(i, 4))
    s = p.stats()
    assert s["spill_bytes"] <= 2 * ent and s["spill_entries"] <= 2


@pytest.mark.parametrize("dtype", ["native", "int8"])
def test_pool_device_bytes_measure_placed_entries(monkeypatch, dtype):
    """device_bytes() sums the stored arrays' shards per device: it matches
    bytes_used when entries are device-resident (the accelerator
    placement, steered here on the CPU backend) and counts host-resident
    entries nowhere."""
    kv = _kv_tree(3, shape=(1, 2, 8, 2, 4))
    host = HistoryKVPool(slots=4, dtype=dtype)
    host.put("a", "f", kv)
    assert host.device_bytes() == {}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dev = HistoryKVPool(slots=4, dtype=dtype)
    dev.put("a", "f", kv)
    dev.put("b", "f", _kv_tree(4, shape=(1, 2, 8, 2, 4)))
    assert dev.device_bytes() == {jax.devices()[0]: dev.bytes_used}


def test_pool_stale_returns_extension_basis():
    p = HistoryKVPool(slots=4)
    p.put("u", "f1", _sized_kv(1, 4), hist_window=np.arange(8, dtype=np.int32))
    kv, status, basis = p.lookup("u", "f2", want_basis=True)
    assert kv is None and status == "stale"
    np.testing.assert_array_equal(basis.hist_window, np.arange(8))
    np.testing.assert_array_equal(np.asarray(basis.kv["k"]),
                                  _sized_kv(1, 4)["k"])
    assert len(p) == 0                   # stale entry is dropped either way


# ---------------------------------------------------------------------------
# 3. incremental-extension substrate
# ---------------------------------------------------------------------------

def test_causal_q_offset_matches_monolithic():
    """Suffix rows of a causal pass == causal attention of just those rows
    with q_offset, for all three impls (the extend_history substrate)."""
    S, P = 128, 37
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (2, S, 4, 32), jnp.float32)
    k = jax.random.normal(ks[1], (2, S, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, S, 2, 32), jnp.float32)
    full = A.reference_attention(q, k, v, "causal")[:, P:]
    ref = A.reference_attention(q[:, P:], k, v, "causal", q_offset=P)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(full))
    ch = A.chunked_attention(q[:, P:], k, v, "causal", q_chunk=32, k_chunk=32,
                             q_offset=P)
    np.testing.assert_allclose(np.asarray(ch), np.asarray(full),
                               atol=2e-5, rtol=2e-5)
    from repro.kernels.flash_attention import ops as fa_ops
    pl = fa_ops.flash_attention(q[:, P:], k, v, "causal", q_offset=P,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(pl), np.asarray(full),
                               atol=2e-5, rtol=2e-5)


def test_chunked_block_skip_unchanged_numerics():
    """The exact-causal block skip must not change chunked outputs (skipped
    blocks were numerically inert in the online softmax)."""
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (1, 200, 4, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 200, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 200, 2, 32), jnp.float32)
    for mode, kw in (("causal", {}), ("sumi", {"n_history": 150})):
        ref = A.reference_attention(q, k, v, mode, **kw)
        ch = A.chunked_attention(q, k, v, mode, q_chunk=64, k_chunk=32, **kw)
        np.testing.assert_allclose(np.asarray(ch), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


def test_chunked_visible_blocks_are_trimmed():
    """Structural check of the §Perf claim: the causal/sumi jnp paths visit
    only the mask-visible KV chunks, not all of them."""
    vis = A._visible_kv_blocks("causal", 0, q_chunk=32, k_chunk=32, nk=8,
                               sk=256, n_history=0, q_offset=0)
    assert vis == [0]                      # first q chunk sees one KV chunk
    vis = A._visible_kv_blocks("causal", 7, q_chunk=32, k_chunk=32, nk=8,
                               sk=256, n_history=0, q_offset=0)
    assert vis == list(range(8))           # last sees all
    # cached-candidate path: history chunks + own diagonal only
    vis = A._visible_kv_blocks("sumi", 3, q_chunk=16, k_chunk=32, nk=8,
                               sk=256, n_history=128, q_offset=128)
    assert vis == [0, 1, 2, 3, 5]          # 4 history chunks + self chunk


@pytest.fixture(scope="module")
def climber():
    cfg = dataclasses.replace(
        get_config("climber"), vocab_size=3000, d_model=128, d_ff=256,
        n_heads=4, n_kv_heads=4, head_dim=32,
        climber=ClimberConfig(num_blocks=2, layers_per_block=2))
    params, _ = C.climber_init(jax.random.key(0), cfg)
    ks = jax.random.split(jax.random.key(1), 3)
    batch = {"history": jax.random.randint(ks[0], (2, 64), 0, 3000),
             "candidates": jax.random.randint(ks[1], (2, 16), 0, 3000),
             "side": jax.random.normal(ks[2], (2, 12))}
    return cfg, params, batch


@pytest.mark.parametrize("impl", ["reference", "chunked"])
@pytest.mark.parametrize("prefix_len", [0, 20, 32, 50, 64])
def test_extend_history_bitwise_vs_full_reencode(climber, impl, prefix_len):
    """The acceptance gate: re-encoding only the suffix + side token against
    a cached prefix is bitwise-identical to a full re-encode whenever the
    trusted prefix actually matches (any prefix length, both jnp impls)."""
    cfg, params, batch = climber
    n = batch["history"].shape[1]
    rng = np.random.default_rng(3)
    hist2 = np.array(batch["history"])
    if prefix_len < n:
        hist2[:, prefix_len:] = rng.integers(0, 3000, (2, n - prefix_len))
    b2 = {"history": jnp.asarray(hist2),
          "side": batch["side"] + 0.5}        # side always moves
    kv1 = C.encode_history(params, batch, cfg, impl=impl)
    fresh = C.encode_history(params, b2, cfg, impl=impl)
    ext = C.extend_history(params, kv1, b2, cfg, prefix_len=prefix_len,
                           impl=impl)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), ext, fresh)
    # and the scores built on the extended KV match exactly too
    s_ext = C.score_candidates(params, ext, batch["candidates"], cfg,
                               impl=impl)
    s_new = C.score_candidates(params, fresh, batch["candidates"], cfg,
                               impl=impl)
    np.testing.assert_array_equal(np.asarray(s_ext), np.asarray(s_new))


def test_extend_history_side_only_refresh(climber):
    """The dominant serving case: history window unchanged, side features
    moved (tail-append beyond the window) — prefix_len == n re-encodes one
    token per block and still matches a full re-encode bitwise."""
    cfg, params, batch = climber
    b2 = {"history": batch["history"], "side": batch["side"] * -0.3}
    kv1 = C.encode_history(params, batch, cfg)
    fresh = C.encode_history(params, b2, cfg)
    ext = C.extend_history(params, kv1, b2, cfg,
                           prefix_len=batch["history"].shape[1])
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), ext, fresh)


def test_history_item_kv_is_side_independent(climber):
    """The property the extension relies on: with the side token riding at
    the END of each block prefix, the history-item K/V rows (positions
    0..w-1) must not depend on the side features at all."""
    cfg, params, batch = climber
    kv1 = C.encode_history(params, batch, cfg)
    kv2 = C.encode_history(params, dict(batch, side=batch["side"] + 9.0), cfg)
    w = batch["history"].shape[1] // cfg.climber.num_blocks
    for b in kv1:
        for kk in ("k", "v"):
            np.testing.assert_array_equal(
                np.asarray(kv1[b][kk][:, :, :w]),
                np.asarray(kv2[b][kk][:, :, :w]))
            assert np.abs(np.asarray(kv1[b][kk][:, :, w])
                          - np.asarray(kv2[b][kk][:, :, w])).max() > 1e-6


# ---------------------------------------------------------------------------
# 4. serving stack
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


def _engine(bundle, params, **kw):
    from repro.core.pda import RemoteFeatureStore
    from repro.serving import FlameEngine
    base = dict(n_history=64, buckets=(16, 8), n_streams=2,
                feature_mode="sync",
                store=RemoteFeatureStore(latency_s=0.0, feature_dim=12),
                window_s=0.004, max_batch=2, n_workers=2)
    base.update(kw)
    return FlameEngine(bundle, params, **base)


def test_engine_tail_append_uses_extension(serving_setup):
    """Same user, history extended beyond the model window: the stale hit
    must be served by suffix extension (one token per block), and the
    scores must match a from-scratch engine on the new history."""
    cfg, bundle, params = serving_setup
    eng = _engine(bundle, params, history_cache=True, pool_slots=4,
                  incremental_history=True)
    fresh = _engine(bundle, params, history_cache=True, pool_slots=4)
    rng = np.random.default_rng(0)
    h1 = rng.integers(0, 5000, 80).astype(np.int32)          # window = 64
    h2 = np.concatenate([h1, rng.integers(0, 5000, 8).astype(np.int32)])
    cand = rng.integers(0, 5000, 12).astype(np.int32)
    try:
        eng.serve(h1, cand, user_id=1)                       # encode
        out = eng.serve(h2, cand, user_id=1)                 # stale -> extend
        m = eng.metrics()
        assert m["pool_extensions"] == 1 and m["pool_stale"] == 1
        assert m["dso_dispatches_extend"] == 1
        assert m["dso_dispatches_encode"] == 1               # only the first
        ref = fresh.serve(h2, cand, user_id=9)
        np.testing.assert_allclose(out.astype(np.float32),
                                   ref.astype(np.float32),
                                   atol=2e-3, rtol=2e-3)
        # a subsequent identical request is a plain hit on the extended entry
        again = eng.serve(h2, cand, user_id=1)
        np.testing.assert_array_equal(out, again)
    finally:
        eng.shutdown()
        fresh.shutdown()


def test_engine_unrelated_history_reencodes(serving_setup):
    """A stale hit with NO shared window prefix must fall back to a full
    re-encode (extension buckets exist but none fits)."""
    cfg, bundle, params = serving_setup
    eng = _engine(bundle, params, history_cache=True, pool_slots=4,
                  incremental_history=True, extend_buckets=(64, 32))
    rng = np.random.default_rng(1)
    h1 = rng.integers(0, 5000, 64).astype(np.int32)
    h2 = rng.integers(0, 5000, 64).astype(np.int32)          # fresh draw
    assert h1[0] != h2[0]        # shared prefix < smallest bucket (32)
    cand = rng.integers(0, 5000, 8).astype(np.int32)
    try:
        eng.serve(h1, cand, user_id=2)
        eng.serve(h2, cand, user_id=2)
        m = eng.metrics()
        assert m["pool_extensions"] == 0
        assert m["dso_dispatches_encode"] == 2
    finally:
        eng.shutdown()


def test_engine_partial_prefix_extension(serving_setup):
    """A mid-window history change extends from the largest trusted-prefix
    bucket <= the shared prefix, and scores still match a fresh engine."""
    cfg, bundle, params = serving_setup
    eng = _engine(bundle, params, history_cache=True, pool_slots=4,
                  incremental_history=True, extend_buckets=(64, 32))
    fresh = _engine(bundle, params, history_cache=True, pool_slots=4)
    rng = np.random.default_rng(2)
    h1 = rng.integers(0, 5000, 64).astype(np.int32)
    h2 = h1.copy()
    h2[40:] = rng.integers(0, 5000, 24)                      # shared prefix 40
    cand = rng.integers(0, 5000, 8).astype(np.int32)
    try:
        eng.serve(h1, cand, user_id=3)
        out = eng.serve(h2, cand, user_id=3)                 # extend @ 32
        m = eng.metrics()
        assert m["pool_extensions"] == 1
        ref = fresh.serve(h2, cand, user_id=9)
        np.testing.assert_allclose(out.astype(np.float32),
                                   ref.astype(np.float32),
                                   atol=2e-3, rtol=2e-3)
    finally:
        eng.shutdown()
        fresh.shutdown()


@pytest.mark.parametrize("pool_dtype", ["native", "int8"])
def test_engine_multi_chunk_dedup_correctness(serving_setup, pool_dtype):
    """A request split into same-bucket chunks rides one dispatch with its
    KV rows stacked ONCE (the fused impl dedups on every backend); scores
    must match the full-pass engine and stay bitwise-stable across
    repeats.  The int8 variant exercises the
    (key, fingerprint) dedup token: quantized lookups dequantize to fresh
    arrays, so object identity alone could never match."""
    cfg, bundle, params = serving_setup
    eng = _engine(bundle, params, history_cache=True, pool_slots=4,
                  window_s=0.02, impl="fused", pool_dtype=pool_dtype)
    eng_full = _engine(bundle, params)
    rng = np.random.default_rng(4)
    hist = rng.integers(0, 5000, 64).astype(np.int32)
    cand = rng.integers(0, 5000, 32).astype(np.int32)        # 2x bucket 16
    try:
        a = eng.serve(hist, cand, user_id=5)
        m = eng.metrics()
        assert m["dso_dedup_rows_saved"] >= 1
        b = eng_full.serve(hist, cand)
        np.testing.assert_allclose(a.astype(np.float32),
                                   b.astype(np.float32),
                                   atol=2e-3, rtol=2e-3)
        # repeat-stability must be bitwise hit-to-hit (the int8 miss path
        # scores against the pre-quantization KV, so compare two hits)
        hit1 = eng.serve(hist, cand, user_id=5)
        hit2 = eng.serve(hist, cand, user_id=5)
        np.testing.assert_array_equal(hit1, hit2)
        np.testing.assert_allclose(hit1.astype(np.float32),
                                   a.astype(np.float32),
                                   atol=2e-2, rtol=2e-2)
    finally:
        eng.shutdown()
        eng_full.shutdown()


def test_engine_int8_pool_score_drift_bound(serving_setup):
    """int8 pool entries must keep hit-path scores within the stated drift
    bound of a native pool (the users-per-replica trade documented in
    docs/ARCHITECTURE.md)."""
    cfg, bundle, params = serving_setup
    rng = np.random.default_rng(5)
    hist = rng.integers(0, 5000, 64).astype(np.int32)
    cand = rng.integers(0, 5000, 12).astype(np.int32)
    outs, bytes_ = {}, {}
    for dt in ("native", "int8"):
        eng = _engine(bundle, params, history_cache=True, pool_slots=4,
                      pool_dtype=dt)
        try:
            eng.serve(hist, cand, user_id=6)          # miss: encode + put
            outs[dt] = eng.serve(hist, cand, user_id=6)   # hit through pool
            bytes_[dt] = eng.metrics()["pool_bytes"]
        finally:
            eng.shutdown()
    drift = np.abs(outs["int8"].astype(np.float32)
                   - outs["native"].astype(np.float32)).max()
    assert drift <= INT8_SCORE_DRIFT_BOUND, drift
    assert bytes_["int8"] < bytes_["native"] * 0.62   # bf16-native leaves


def test_engine_byte_budget_evicts_and_reports(serving_setup):
    """pool_budget_bytes bounds the engine's pool; bytes_used surfaces as a
    ServeMetrics gauge and never exceeds the budget."""
    cfg, bundle, params = serving_setup
    probe = _engine(bundle, params, history_cache=True, pool_slots=64)
    rng = np.random.default_rng(6)
    hists = [rng.integers(0, 5000, 64).astype(np.int32) for _ in range(4)]
    cand = rng.integers(0, 5000, 8).astype(np.int32)
    try:
        probe.serve(hists[0], cand, user_id=0)
        entry = probe.metrics()["pool_bytes"]
    finally:
        probe.shutdown()
    budget = int(entry * 2.5)                       # fits 2 entries
    eng = _engine(bundle, params, history_cache=True, pool_slots=64,
                  pool_budget_bytes=budget)
    try:
        for u, h in enumerate(hists):
            eng.serve(h, cand, user_id=u)
        m = eng.metrics()
        assert m["pool_entries"] == 2
        assert m["pool_evictions"] == 2
        assert m["pool_bytes"] <= budget
        assert m["pool_bytes_used"] == m["pool_bytes"]    # ServeMetrics gauge
    finally:
        eng.shutdown()
