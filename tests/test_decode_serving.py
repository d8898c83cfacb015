"""Generative candidate decode (ISSUE 8): oracle-backed decode suite.

Layers of coverage:

  1. step identity — one greedy decode step at ``lengths == S`` is BITWISE
     ``score_candidates(M=V)`` + argmax (reference/chunked; bf16-tight
     allclose for the block-reordered pallas route), and a PADDED beam
     cache decodes bitwise like the unpadded one (masked positions get
     exact-zero softmax weight, the placement-invariance the engine's
     fixed-shape caches rely on);
  2. attention oracle — ``sumi.decode_candidate_attention`` against the
     fp32 ``kernels/flash_decode/ref.decode_with_self`` ground truth;
  3. N-step greedy — an incrementally-grown beam cache
     (``decode_logits`` + ``append_token``) reproduces, token for token, a
     pure-Python decode loop over the MONOLITHIC reference forward (the
     repo's ground-truth path: no beam caches, no scatter, the whole
     sequence re-assembled and re-scored from scratch every step);
  4. beam search — the engine's ``BeamConfig`` result on a toy universe
     equals exhaustive enumeration of every sequence ranked by cumulative
     log-probability (width >= V^(N-1) makes beam search provably exact),
     plus propcheck invariants on ``generate.beam_step``: scores
     monotonically non-increasing, no duplicate live hypotheses, finished
     hypotheses pass through frozen and are never re-expanded;
  5. engine/packing — concurrent multi-request decode is bitwise the
     sequential decode of the same engine, the pack_tails engine emits
     bitwise the unpacked engine's sequences, and a beam evicted from a
     tiny pool mid-generation replays (re-encode + re-append) to the same
     sequences, counted by ``gen_replays``; under dispatch faults and
     eviction storms every decode future resolves and evicted beams
     replay.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tests._propcheck import given, settings, st

from repro.configs import get_config
from repro.core import climber as C
from repro.core.pda import RemoteFeatureStore
from repro.core import sumi
from repro.kernels.flash_decode import ref as fd_ref
from repro.models import build_model
from repro.serving import FlameEngine, ServeRequest
from repro.serving.api import BeamConfig, TopKConfig
from repro.serving.faults import FaultInjector
from repro.serving import generate as G
from repro.serving.scheduler import run_workload_async
from repro.types import ClimberConfig

N_HIST = 16
VOCAB = 64


def _cfg():
    return dataclasses.replace(
        get_config("climber"), vocab_size=VOCAB, d_model=64, d_ff=128,
        n_heads=2, n_kv_heads=2, head_dim=32,
        climber=ClimberConfig(num_blocks=2, layers_per_block=2))


@pytest.fixture(scope="module")
def climber_setup():
    cfg = _cfg()
    bundle = build_model(cfg)
    params, _ = bundle.init(jax.random.key(0))
    ks = jax.random.split(jax.random.key(1), 3)
    batch = {"history": jax.random.randint(ks[0], (1, N_HIST), 0, VOCAB),
             "side": jax.random.normal(ks[2], (1, 12))}
    return cfg, bundle, params, batch


def _s0(cfg):
    """Per-block cache length: history sub-sequence + the side token."""
    return N_HIST // cfg.climber.num_blocks + 1


def _pad_tree(kv, extra: int):
    """Pad every [B,L,S,Hkv,D] leaf by ``extra`` sequence slots (axis 2)
    with a NON-ZERO fill: equality through the padded cache then proves
    the length mask, not lucky zeros."""
    return jax.tree.map(
        lambda a: jnp.pad(a, [(0, 0), (0, 0), (0, extra), (0, 0), (0, 0)],
                          constant_values=3.75), kv)


def _step_logprobs(probs_bmt: np.ndarray) -> np.ndarray:
    """The engine's ranking statistic: fp64 log-softmax over the token
    universe of the per-candidate TASK-SUM of sigmoid probabilities."""
    return G.log_softmax(np.asarray(probs_bmt, np.float32).sum(-1))


# ---------------------------------------------------------------------------
# 1. one decode step IS score_candidates + argmax
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["reference", "chunked", "pallas"])
def test_decode_step_is_score_candidates(climber_setup, impl):
    cfg, bundle, params, batch = climber_setup
    kv = bundle.encode_history(params, batch, impl=impl)
    cand = jax.random.randint(jax.random.key(7), (1, 8), 0, VOCAB)
    lengths = np.asarray([_s0(cfg)], np.int32)
    want = np.asarray(bundle.score_candidates(params, kv, cand, impl=impl))
    got = np.asarray(bundle.decode_logits(params, kv, cand, lengths,
                                          impl=impl))
    if impl == "pallas":
        np.testing.assert_allclose(got, want, atol=5e-3, rtol=5e-3)
    else:
        np.testing.assert_array_equal(got, want)
    # the greedy decision is the score-path argmax
    assert int(np.argmax(_step_logprobs(got[0]))) == \
        int(np.argmax(_step_logprobs(want[0])))


@pytest.mark.parametrize("impl", ["reference", "chunked"])
def test_padded_cache_decodes_bitwise(climber_setup, impl):
    cfg, bundle, params, batch = climber_setup
    kv = bundle.encode_history(params, batch, impl=impl)
    cand = jax.random.randint(jax.random.key(8), (1, 6), 0, VOCAB)
    lengths = np.asarray([_s0(cfg)], np.int32)
    want = np.asarray(bundle.decode_logits(params, kv, cand, lengths,
                                           impl=impl))
    got = np.asarray(bundle.decode_logits(params, _pad_tree(kv, 5), cand,
                                          lengths, impl=impl))
    np.testing.assert_array_equal(got, want)


def test_decode_attention_matches_fp32_oracle():
    """sumi.decode_candidate_attention (reference route) against the
    kernels/flash_decode fp32 ground truth, padded rows included."""
    rng = np.random.default_rng(3)
    b, m, s, h, hkv, d = 3, 5, 11, 4, 2, 8
    q = rng.standard_normal((b, m, h, d)).astype(np.float32)
    kh = rng.standard_normal((b, s, hkv, d)).astype(np.float32)
    vh = rng.standard_normal((b, s, hkv, d)).astype(np.float32)
    kc = rng.standard_normal((b, m, hkv, d)).astype(np.float32)
    vc = rng.standard_normal((b, m, hkv, d)).astype(np.float32)
    lengths = np.asarray([11, 7, 4], np.int32)
    got = np.asarray(sumi.decode_candidate_attention(
        jnp.asarray(q), jnp.asarray(kh), jnp.asarray(vh), jnp.asarray(kc),
        jnp.asarray(vc), lengths, impl="reference"))
    want = np.asarray(fd_ref.decode_with_self(
        jnp.asarray(q), jnp.asarray(kh), jnp.asarray(vh),
        jnp.asarray(lengths), jnp.asarray(kc), jnp.asarray(vc)))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# 3. N-step greedy vs the monolithic pure-Python oracle
# ---------------------------------------------------------------------------

def _oracle_step_probs(params, batch, cfg, tokens, universe):
    """Ground-truth probabilities for the next decode step, WITHOUT beam
    caches: re-assemble every block's full sequence — history sub-sequence
    + side token + the tokens generated so far + the candidate universe —
    and run the monolithic SUMI forward from scratch (reference impl).
    The generated tokens join the causal prefix (position s0+g for token
    g), the universe sits at the shared next position, exactly the
    layout the incremental decode path maintains in its caches."""
    emb = params["embed"]["embedding"]
    tok_e = jnp.take(emb, jnp.asarray([list(tokens)], jnp.int32), axis=0) \
        if tokens else None
    cand_e = jnp.take(emb, jnp.asarray([list(universe)], jnp.int32), axis=0)
    n_hist = _s0(cfg) + len(tokens)
    outs = []
    for i, xb in enumerate(C._history_block_inputs(params, batch, cfg)):
        parts = [xb] + ([tok_e.astype(xb.dtype)] if tok_e is not None
                        else []) + [cand_e.astype(xb.dtype)]
        seq = jnp.concatenate(parts, axis=1)
        out = C._block_forward(params["blocks"][f"b{i}"], seq, n_hist, cfg,
                               "reference")
        outs.append(out[:, n_hist:])
    h = jnp.stack(outs, axis=2)
    return np.asarray(jax.nn.sigmoid(C._fuse_and_head(params, h, cfg)))


@pytest.mark.parametrize("impl", ["reference", "chunked"])
def test_nstep_greedy_matches_monolithic_oracle(climber_setup, impl):
    cfg, bundle, params, batch = climber_setup
    steps, universe = 5, np.arange(12, dtype=np.int32)
    s0 = _s0(cfg)
    kv = _pad_tree(bundle.encode_history(params, batch, impl=impl), steps)
    tokens, oracle_tokens = [], []
    for g in range(steps):
        lengths = np.asarray([s0 + g], np.int32)
        probs = np.asarray(bundle.decode_logits(
            params, kv, universe[None], lengths, impl=impl))
        want = _oracle_step_probs(params, batch, cfg, oracle_tokens,
                                  universe)
        if impl == "reference":
            # same fp32 math, different assembly: monolithic re-encode vs
            # incrementally appended cache — bitwise is the contract
            np.testing.assert_array_equal(probs, want)
        else:
            np.testing.assert_allclose(probs, want, atol=1e-6, rtol=1e-6)
        lp, wlp = _step_logprobs(probs[0]), _step_logprobs(want[0])
        tok = int(universe[np.argmax(lp)])
        oracle_tokens.append(int(universe[np.argmax(wlp)]))
        assert tok == oracle_tokens[-1], f"diverged at step {g}"
        tokens.append(tok)
        kv = bundle.append_token(params, kv, np.asarray([[tok]], np.int32),
                                 lengths, impl=impl)
    assert tokens == oracle_tokens


# ---------------------------------------------------------------------------
# 4a. propcheck: beam_step invariants
# ---------------------------------------------------------------------------

@given(st.integers(0, 10_000), st.integers(1, 5), st.integers(2, 6),
       st.integers(0, 1))
@settings(max_examples=60, deadline=None)
def test_beam_step_invariants(seed, width, vocab, use_eos):
    rng = np.random.default_rng(seed)
    width = min(width, vocab)
    universe = np.sort(rng.choice(50, size=vocab, replace=False))
    eos = int(universe[0]) if use_eos else None
    # seed: top-width distinct single-token hypotheses
    lp0 = G.log_softmax(rng.standard_normal(vocab))
    order = np.argsort(-lp0, kind="stable")[:width]
    cum = lp0[order]
    seqs = [(int(universe[o]),) for o in order]
    fin = np.asarray([eos is not None and t[0] == eos for t in seqs])
    for _ in range(3):
        step_lp = G.log_softmax(rng.standard_normal((len(cum), vocab)),
                                axis=-1)
        new_cum, new_seqs, new_fin, parents = G.beam_step(
            cum, seqs, fin, step_lp, width, eos, universe)
        # scores monotonically non-increasing (log-probs are <= 0)
        assert new_cum.max() <= cum.max() + 1e-9
        assert (np.diff(new_cum) <= 1e-12).all(), "result not best-first"
        # no duplicate live hypotheses
        live = [new_seqs[i] for i in range(len(new_seqs)) if not new_fin[i]]
        assert len(live) == len(set(live))
        for slot in range(len(new_cum)):
            p = int(parents[slot])
            if fin[p]:
                # finished hypotheses pass through frozen: same tokens,
                # same score, still finished — never re-expanded
                assert new_seqs[slot] == seqs[p]
                assert new_cum[slot] == cum[p]
                assert new_fin[slot]
            else:
                assert new_seqs[slot][:-1] == seqs[p]
                assert new_seqs[slot][-1] in universe
        cum, seqs, fin = new_cum, new_seqs, new_fin


# ---------------------------------------------------------------------------
# engine fixtures
# ---------------------------------------------------------------------------

def _engine(bundle, params, **kw):
    base = dict(n_history=N_HIST, buckets=(8, 4), n_streams=2,
                feature_mode="off",
                store=RemoteFeatureStore(latency_s=0.0, feature_dim=12),
                window_s=0.01, max_batch=4, n_workers=4,
                history_cache=True, pool_slots=32,
                generate=6, gen_vocab=16)
    base.update(kw)
    return FlameEngine(bundle, params, **base)


@pytest.fixture(scope="module")
def engines(climber_setup):
    cfg, bundle, params, _ = climber_setup
    plain = _engine(bundle, params)
    packed = _engine(bundle, params, pack_tails=True)
    yield plain, packed
    plain.shutdown()
    packed.shutdown()


def _requests(n, seed=0):
    """Ragged generative traffic: universes of 3..11 ids (sub-bucket tails
    so pack_tails has something to pack), mixed top-k / beam."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        m = int(rng.integers(3, 12))
        reqs.append({
            "history": rng.integers(0, VOCAB, N_HIST).astype(np.int32),
            "candidates": rng.integers(0, VOCAB, m).astype(np.int32),
            "user_id": int(i),
            "generate": (TopKConfig(k=2, steps=4) if i % 2 else
                         BeamConfig(width=3, steps=4)),
        })
    return reqs


# ---------------------------------------------------------------------------
# 4b. beam search == exhaustive enumeration
# ---------------------------------------------------------------------------

def test_engine_beam_equals_exhaustive(climber_setup, engines):
    """width >= V^(steps-1) keeps every prefix alive, so beam search must
    return exactly the global top-width of ALL V^steps sequences ranked by
    cumulative log-probability — computed here by brute-force enumeration
    through the model-level decode surface."""
    cfg, bundle, params, _ = climber_setup
    eng, _ = engines
    universe = np.asarray([5, 11, 23, 42], np.int32)   # V=4
    steps, width = 3, 16                               # 16 = 4^2
    rng = np.random.default_rng(17)
    hist = rng.integers(0, VOCAB, N_HIST).astype(np.int32)
    out = eng.serve(hist, candidates=universe, user_id=777,
                    generate=BeamConfig(width=width, steps=steps))
    assert out.shape == (width, steps)

    # exhaustive oracle: grow every prefix's cache explicitly.  The bundle
    # fns are JIT-WRAPPED: on this backend eager execution rounds matmuls
    # differently from compiled code (~1e-2 on KV leaves), while compiled
    # execution is row-wise batch-invariant — jitted calls here reproduce
    # the engine's AOT executors bitwise, so the comparison stays exact.
    dec = jax.jit(lambda kvt, c, l: bundle.decode_logits(
        params, kvt, c, l, impl=eng.impl))
    app = jax.jit(lambda kvt, t, l: bundle.append_token(
        params, kvt, t, l, impl=eng.impl))
    enc = jax.jit(lambda h, s: bundle.encode_history(
        params, {"history": h, "side": s}, impl=eng.impl))
    side = eng._side_features(hist)
    root = jax.tree.map(
        lambda a: jnp.pad(a, [(0, 0), (0, 0), (0, steps), (0, 0), (0, 0)]),
        enc(jnp.asarray(hist[None]), jnp.asarray(side)))
    s0 = _s0(cfg)
    level = {(): (0.0, root)}
    table = {}
    for g in range(steps):
        nxt = {}
        lens = np.asarray([s0 + g], np.int32)
        for prefix, (score, kv) in level.items():
            probs = np.asarray(dec(kv, universe[None], lens))
            lp = _step_logprobs(probs[0])
            for j, tok in enumerate(universe):
                seq = prefix + (int(tok),)
                if g < steps - 1:
                    nxt[seq] = (score + lp[j], app(
                        kv, np.asarray([[tok]], np.int32), lens))
                else:
                    table[seq] = score + lp[j]
        level = nxt
    ranked = sorted(table.items(), key=lambda kvp: -kvp[1])
    want = np.asarray([list(seq) for seq, _ in ranked[:width]], np.int32)
    np.testing.assert_array_equal(out, want)
    # and the returned rows really are the global top-width by score
    eng_scores = np.asarray([table[tuple(int(t) for t in row)]
                             for row in out])
    assert (np.diff(eng_scores) <= 0).all(), "rows not best-first"


# ---------------------------------------------------------------------------
# 5. engine: packed / concurrent / sequential equality + pool interaction
# ---------------------------------------------------------------------------

def test_concurrent_packed_decode_equals_sequential(engines):
    plain, packed = engines
    reqs = _requests(6, seed=1)
    # sequential ground truth: one request in flight at a time
    seq_out = []
    for r in reqs:
        seq_out.append(plain.serve(r["history"], candidates=r["candidates"],
                                   user_id=r["user_id"],
                                   generate=r["generate"]))
    # concurrent on the same engine (warm pool): placement in coalesced /
    # packed dispatches must not change a single token
    res = run_workload_async(plain, reqs)
    for got, want in zip(res["outputs"], seq_out):
        np.testing.assert_array_equal(got, want)
    # concurrent on the pack_tails engine: segment-packed per-step ragged
    # batching of in-flight beams, still bitwise
    res_p = run_workload_async(packed, reqs)
    for got, want in zip(res_p["outputs"], seq_out):
        np.testing.assert_array_equal(got, want)
    assert packed.metrics()["dso_packed_segments"] > 0


def test_evicted_beam_replays_to_same_sequences(climber_setup, engines):
    """A beam whose parked cache is evicted (or rejected) mid-generation
    re-encodes its base history and replays its appends — same tokens, at
    replay cost, counted by ``gen_replays``."""
    cfg, bundle, params, _ = climber_setup
    plain, _ = engines
    tiny = _engine(bundle, params, pool_slots=1)
    try:
        rng = np.random.default_rng(23)
        hist = rng.integers(0, VOCAB, N_HIST).astype(np.int32)
        universe = rng.integers(0, VOCAB, 9).astype(np.int32)
        gen = BeamConfig(width=3, steps=5)
        want = plain.serve(hist, candidates=universe, user_id=901,
                           generate=gen)
        got = tiny.serve(hist, candidates=universe, user_id=901,
                         generate=gen)
        np.testing.assert_array_equal(got, want)
        assert tiny.metrics().get("gen_replays", 0) > 0, \
            "a 1-slot pool must force at least one beam replay"
    finally:
        tiny.shutdown()


@pytest.mark.parametrize("impl", ["chunked", "fused"])
def test_decode_chaos_zero_hung_and_beams_replay(climber_setup, impl):
    """Chaos on packed generative decode over an int8 pool: transient
    dispatch faults retry decode-step dispatches and eviction storms drop
    parked beam caches mid-generation.  Every future resolves (result or
    error), and evicted beams re-decode from the root (``gen_replays``)
    instead of failing their requests."""
    cfg, bundle, params, _ = climber_setup
    eng = _engine(bundle, params, impl=impl, pool_dtype="int8",
                  pack_tails=True,
                  faults=FaultInjector.parse("dispatch:0.1,evict:0.15",
                                             seed=43))
    reqs = _requests(12, seed=4)
    hung = 0
    try:
        for _ in range(2):
            res = run_workload_async(eng, reqs, tolerate_errors=True,
                                     result_timeout_s=120.0)
            hung += res["hung"]
            assert res["resolved"] + res["rejected"] + res["failed"] \
                == len(reqs), res
        m = eng.metrics()
    finally:
        eng.shutdown()
    assert hung == 0
    assert m["fault_dispatch_fired"] >= 1 and m["fault_evict_fired"] >= 1, m
    assert m.get("gen_replays", 0) >= 1, m


def test_generate_request_validation(engines):
    eng, _ = engines
    hist = np.arange(N_HIST, dtype=np.int32)
    with pytest.raises(ValueError, match="capacity"):
        eng.serve(hist, generate=TopKConfig(k=2, steps=99))
    with pytest.raises(ValueError, match="top-k"):
        # top-k can seed at most |universe| independent greedy beams
        eng.serve(hist, candidates=np.asarray([1, 2, 3], np.int32),
                  generate=TopKConfig(k=8, steps=2))
    with pytest.raises(ValueError, match="TopKConfig"):
        eng.serve(hist, generate=42)


def test_generate_metrics_surface(engines):
    """After the suites above, the decode observability must be populated:
    decode rounds and generated tokens counted, no beams left behind."""
    eng, _ = engines
    m = eng.metrics()
    assert m["decode_steps"] > 0
    assert m["gen_tokens"] > 0
    assert m["beams_in_flight"] == 0
    assert m.get("dso_dispatches_decode", 0) > 0
    assert m.get("dso_dispatches_append", 0) > 0
