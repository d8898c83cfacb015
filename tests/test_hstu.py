"""HSTU served through FlameEngine against the benchmark's plain reference
(``flamebench/families/hstu_reference.py``: float32, full causal forward
per row, nothing of the program), at a small size on seeded random
weights: d 32, 2 layers, 2 heads of 16, window 24, slates up to 8.

Paths: a miss (encode -> cached), repeat hits, a history grown past the
window (extend at the full window -> cached), a window edited inside
(extend of a real suffix through the pointwise kernel's extend mode ->
cached), packed tails; native, bf16 and int8 pools.  Also: a request that
carries PDA's own timestamps scores as one that carries none, other times
move the scores, the pool keeps the time leaf as int32 through an extend,
and the engine refuses what HSTU has no surface for.
"""
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pda
from repro.models import build_model
from repro.serving import create_engine
from repro.serving.api import ServeRequest, TopKConfig
from repro.types import HSTUConfig, ModelConfig

ROOT = os.path.join(os.path.dirname(__file__), "..")
N = 24
VOCAB = 500
MODEL = {"n_layers": 2, "d_model": 32, "n_heads": 2, "n_kv_heads": 2,
         "head_dim": 16, "d_ff": 0, "vocab_size": VOCAB, "norm": "layernorm",
         "hstu": {"max_seq_len": N, "num_time_buckets": 128,
                  "num_tasks": 3}}
#: |served - reference| per pool dtype, f32 weights.  native: the same
#: f32 math in another order (blocked sums, kernel vs einsum), so f32
#: rounding; bf16: K/V rounded to 8 mantissa bits (2**-9 relative) in the
#: pool, 0.0022 read; int8: K/V on 255 levels of each (layer, head)'s
#: absmax, 0.020 read at this width (16-wide heads, 24 positions: the
#: LayerNorm of A V magnifies the rounding of a short average)
TOL = {"native": 2e-5, "bf16": 1e-2, "int8": 4e-2}


def _load_reference():
    path = os.path.join(ROOT, "flamebench", "families", "hstu_reference.py")
    spec = importlib.util.spec_from_file_location("hstu_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load_reference()


def _cfg():
    m = dict(MODEL)
    return ModelConfig(name="hstu-test", family="hstu",
                       hstu=HSTUConfig(**m.pop("hstu")), **m)


@pytest.fixture(scope="module")
def setup():
    bundle = build_model(_cfg())
    params, _ = bundle.init(jax.random.key(3))
    ks = jax.random.split(jax.random.key(4), 4)
    # seeded random weights with biases and bias tables that matter
    layers = dict(params["layers"])
    layers["uvqk_b"] = 0.1 * jax.random.normal(ks[0],
                                               layers["uvqk_b"].shape)
    layers["pos_bias"] = jax.random.normal(ks[1], layers["pos_bias"].shape)
    layers["time_bias"] = jax.random.normal(ks[2],
                                            layers["time_bias"].shape)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          dict(params, layers=layers))
    return bundle, params


def _engine(bundle, params, **kw):
    opts = dict(n_history=N, buckets=(8, 4), impl="fused",
                history_cache=True, pool_dtype="int8",
                incremental_history=True, pack_tails=True, max_batch=4)
    opts.update(kw)
    return create_engine("flame", bundle, params, **opts)


def _reference(params, history, user, cands, times=None):
    ids = history[:N]
    t = REF.action_times(user, ids) if times is None else times[:N]
    return REF.scores(params, MODEL, ids, t, cands, block=8)


def _sessions(seed=0):
    """Requests of three users: misses, hits, growth past the window and
    an edit inside it (window position 20, past the 3n/4 extend rung)."""
    rng = np.random.default_rng(seed)
    hist = {u: rng.integers(0, VOCAB, N + 6).astype(np.int32)
            for u in range(3)}
    out = []
    for it in range(15):
        u = it % 3
        if it in (6, 7):                          # grown past the window
            hist[u] = np.concatenate(
                [hist[u], rng.integers(0, VOCAB, 2).astype(np.int32)])
        if it == 11:                              # edited inside it
            hist[u] = hist[u].copy()
            hist[u][20] = (hist[u][20] + 1) % VOCAB
        m = int(rng.integers(1, 9))
        out.append((u, hist[u], rng.integers(0, VOCAB, m).astype(np.int32)))
    return out


@pytest.mark.parametrize("pool_dtype", ["native", "bf16", "int8"])
def test_engine_scores_match_the_reference(setup, pool_dtype):
    bundle, params = setup
    eng = _engine(bundle, params, pool_dtype=pool_dtype)
    try:
        worst = 0.0
        for u, hist, cands in _sessions():
            out = eng.submit(ServeRequest(history=hist, candidates=cands,
                                          user_id=u)).result().output
            want = _reference(params, hist, u, cands)
            assert out.shape == want.shape
            worst = max(worst, float(np.abs(out - want).max()))
        m = eng.metrics()
    finally:
        eng.shutdown()
    assert worst < TOL[pool_dtype]
    assert m["pool_hits"] > 0 and m["dso_dispatches_encode"] > 0
    assert m["dso_dispatches_extend"] >= 2        # growth and the edit
    assert m["times_n"] == m["dso_chunks_encode"] + m["dso_chunks_extend"]
    assert m["features_n"] == 0                   # no side-feature query


def test_packed_tails_coalesce_and_match(setup):
    """Concurrent requests: tails of several users pack into shared rows
    (two 8-aligned segments to a 16-slot row), each segment scored
    against its own user's pooled history."""
    bundle, params = setup
    eng = _engine(bundle, params, pool_dtype="native", buckets=(16,),
                  window_s=0.05)
    rng = np.random.default_rng(7)
    reqs = [(u, rng.integers(0, VOCAB, N).astype(np.int32),
             rng.integers(0, VOCAB, int(rng.integers(1, 8)))
             .astype(np.int32)) for u in range(6)]
    try:
        for u, h, c in reqs:                      # pool every user first
            eng.submit(ServeRequest(history=h, candidates=c[:1],
                                    user_id=u)).result()
        futs = [eng.submit(ServeRequest(history=h, candidates=c, user_id=u))
                for u, h, c in reqs]
        outs = [f.result().output for f in futs]
        m = eng.metrics()
    finally:
        eng.shutdown()
    for (u, h, c), out in zip(reqs, outs):
        np.testing.assert_allclose(out, _reference(params, h, u, c),
                                   atol=TOL["native"], rtol=0)
    assert m["dso_packed_segments"] > m["dso_packed_rows"]
    assert m["dso_qblocks_mixed"] > 0     # shared rows: a block, two users


def test_request_timestamps_equal_to_pdas_score_the_same(setup):
    bundle, params = setup
    eng = _engine(bundle, params, pool_dtype="native")
    rng = np.random.default_rng(11)
    hist = rng.integers(0, VOCAB, N + 3).astype(np.int32)
    cands = rng.integers(0, VOCAB, 6).astype(np.int32)
    own = pda.action_times(5, hist)
    other = own - rng.integers(0, 10**6, own.shape).astype(np.int32)
    try:
        plain = eng.submit(ServeRequest(history=hist, candidates=cands,
                                        user_id=5)).result().output
        given = eng.submit(ServeRequest(history=hist, candidates=cands,
                                        user_id=5, timestamps=own)
                           ).result().output
        moved = eng.submit(ServeRequest(history=hist, candidates=cands,
                                        user_id=5, timestamps=other)
                           ).result().output
    finally:
        eng.shutdown()
    np.testing.assert_array_equal(given, plain)
    assert np.abs(moved - plain).max() > 1e-4
    np.testing.assert_allclose(
        moved, _reference(params, hist, 5, cands, times=other),
        atol=TOL["native"], rtol=0)


def test_pda_times_are_the_references_rule():
    ids = np.arange(4096, dtype=np.int32) * 7919
    for user in (0, 5, 2**40 + 3, None):
        np.testing.assert_array_equal(pda.action_times(user, ids),
                                      REF.action_times(user, ids))


def test_pool_time_leaf_survives_extend_unquantized(setup):
    bundle, params = setup
    eng = _engine(bundle, params, pool_dtype="int8")
    rng = np.random.default_rng(13)
    hist = rng.integers(0, VOCAB, N).astype(np.int32)
    cands = rng.integers(0, VOCAB, 3).astype(np.int32)
    try:
        eng.submit(ServeRequest(history=hist, candidates=cands,
                                user_id=9)).result()
        edited = hist.copy()
        edited[N - 2] = (edited[N - 2] + 1) % VOCAB
        eng.submit(ServeRequest(history=edited, candidates=cands,
                                user_id=9)).result()
        assert eng.metrics()["pool_extensions"] == 1
        key, fp = eng._pool_key(ServeRequest(history=edited, user_id=9))
        raw = eng.history_pool.peek(key, fp, raw=True)
    finally:
        eng.shutdown()
    t = np.asarray(raw["t"])
    assert t.dtype == np.int32
    np.testing.assert_array_equal(t[0], pda.action_times(9, edited))
    assert np.asarray(raw["k"][0]).dtype == np.int8
    assert np.asarray(raw["v"][0]).dtype == np.int8


def test_engine_refuses_generation_naming_the_model(setup):
    bundle, params = setup
    with pytest.raises(ValueError, match=r"hstu-test.*hstu"):
        _engine(bundle, params, generate=2)
    eng = _engine(bundle, params)
    try:
        with pytest.raises(ValueError, match="generative capacity"):
            eng.submit(ServeRequest(
                history=np.zeros(N, np.int32), user_id=1,
                generate=TopKConfig(k=1, steps=1))).result()
    finally:
        eng.shutdown()


def test_engine_takes_planes_from_the_bundle(setup):
    """The engine names no model family: HSTU's planes are (history,
    times), Climber's (history, side), both declared by the bundle."""
    bundle, _ = setup
    assert [(p.name, p.source) for p in bundle.host_planes] == \
        [("history", "window"), ("times", "action_times")]
    src = open(os.path.join(ROOT, "src", "repro", "serving",
                            "engine.py")).read()
    assert not re.search(r"core\.climber|core import climber|hstu", src)
