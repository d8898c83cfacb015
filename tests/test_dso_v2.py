"""DSO v2: segment-packed ragged dispatch + deadline-aware flushing.

Layers of coverage:

  1. packer fuzz — :class:`SegmentPacker` placements never split a segment
     across rows (a segment IS one request's chunk, so no segment ever
     crosses a request boundary), never overlap within a row, never exceed
     the row/KV capacity, and same-identity segments share one KV slot;
  2. EDF flush order — pending chunks pop earliest-deadline-first with a
     shortest-remaining-work tie-break (deadline-less chunks last), and
     deadline overruns land in the ``deadline_misses`` metric;
  3. model-level packing parity — ``score_candidates`` with a
     per-candidate seg index is BITWISE identical to the unpacked
     per-user rows, per impl reference/chunked/fused, across ragged
     segment layouts including 1-candidate segments;
  4. engine level — the packed engine's concurrent scores are bitwise
     the same engine's sequential scores (the coalescing contract; one
     executable, placement-invariant), packed-vs-unpacked engines agree
     at the cross-AOT-executable tolerance with ``padded_fraction``
     reduced, and the quantized extend basis ships raw (no host dequant);
  5. per-row dispatch — KV rows are handed to the executor one array per
     slot and concatenated in graph, device-output families return their
     rows split, so one dispatch is one launch with no eager device op,
     and the pool's stored arrays reach the launch themselves.
"""
import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tests._propcheck import given, settings, st

from repro.configs import get_config
from repro.core import dso as dso_mod
from repro.core.dso import (CoalescePolicy, CoalescingOrchestrator,
                            SegmentPacker, _PendingChunk, per_row_signature)
from repro.core.pda import RemoteFeatureStore
from repro.models import build_model
from repro.serving import (DeadlineExceeded, FlameEngine, ServeMetrics,
                           ServeRequest)
from repro.serving import engine as engine_mod
from repro.serving.kv_cache import (HistoryKVPool, _stored_arrays,
                                    dequantize_kv, quantize_kv, raw_kv_view)
from repro.serving.scheduler import (TrafficConfig, generate_traffic,
                                     run_workload_async)
from repro.types import ClimberConfig


@pytest.fixture(scope="module")
def climber_setup():
    cfg = dataclasses.replace(
        get_config("climber"), vocab_size=10_000, d_model=64, d_ff=128,
        n_heads=2, n_kv_heads=2, head_dim=32,
        climber=ClimberConfig(num_blocks=2, layers_per_block=2))
    bundle = build_model(cfg)
    params, _ = bundle.init(jax.random.key(0))
    return cfg, bundle, params


def _store():
    return RemoteFeatureStore(latency_s=0.0, feature_dim=12)


def _flame(bundle, params, **kw):
    base = dict(n_history=64, buckets=(32, 16), n_streams=2,
                feature_mode="off", store=_store(), window_s=0.01,
                max_batch=4, n_workers=4, history_cache=True, pool_slots=32)
    base.update(kw)
    return FlameEngine(bundle, params, **base)


# ---------------------------------------------------------------------------
# 1. packer fuzz
# ---------------------------------------------------------------------------

SEGMENTS = st.lists(st.tuples(st.integers(1, 16), st.integers(0, 5)),
                    min_size=1, max_size=40)


@given(SEGMENTS, st.integers(1, 8), st.integers(1, 8))
@settings(max_examples=150, deadline=None)
def test_packer_invariants(segs, max_rows, max_kv):
    bucket = 16
    packer = SegmentPacker(bucket, max_rows, max_kv)
    placed = []
    for valid, ident in segs:
        p = packer.try_add(valid, ident)
        if p is not None:
            placed.append((valid, ident, p))
    assert placed, "an empty packer must accept any bucket-sized segment"
    rows = {}
    for valid, ident, (row, off, slot) in placed:
        # a segment never crosses a row (request) boundary
        assert 0 <= row < max_rows
        assert 0 <= off and off + valid <= bucket
        # same identity -> same KV slot, distinct identities stay bounded
        assert slot == packer.slot_of[ident]
        rows.setdefault(row, []).append((off, off + valid))
    assert packer.n_slots <= max_kv
    assert len(rows) == packer.n_rows <= max_rows
    for intervals in rows.values():
        intervals.sort()
        for (a0, a1), (b0, b1) in zip(intervals, intervals[1:]):
            assert a1 <= b0, "segments overlap within a row"
    # fill accounting matches the placements
    for row, intervals in rows.items():
        assert packer.fills[row] == sum(b - a for a, b in intervals)


def test_packer_rejects_oversized_and_fills():
    p = SegmentPacker(8, max_rows=2, max_kv=2)
    with pytest.raises(ValueError):
        p.try_add(9, "a")
    assert p.try_add(8, "a") == (0, 0, 0)
    assert p.try_add(5, "b") == (1, 0, 1)
    assert p.try_add(4, "a") is None        # no row has 4 slots left
    assert p.try_add(3, "c") is None        # KV capacity exhausted
    assert p.try_add(3, "b") == (1, 5, 1)   # existing ident still packs
    assert p.is_full()


# ---------------------------------------------------------------------------
# 2. EDF ordering + deadline accounting
# ---------------------------------------------------------------------------

@given(st.lists(st.tuples(st.integers(0, 1000), st.integers(1, 64)),
                min_size=2, max_size=12))
@settings(max_examples=100, deadline=None)
def test_pending_chunk_edf_ordering(items):
    """Heap order: earliest deadline first (None last), then shortest
    remaining work, then FIFO sequence."""
    chunks = []
    for dl, rem in items:
        chunks.append(_PendingChunk(
            args=(), future=None,
            deadline=None if dl == 0 else float(dl), remaining=rem))
    got = sorted(chunks)
    keys = [(c.deadline if c.deadline is not None else float("inf"),
             c.remaining, c.seq) for c in got]
    assert keys == sorted(keys)


def test_orchestrator_flushes_in_edf_order():
    """Preloaded same-bucket chunks dispatch earliest-deadline-first with
    SRW tie-breaks, not FIFO."""
    order = []

    def build(bucket, batch):
        fn = jax.jit(lambda x: x * 2.0).lower(
            jax.ShapeDtypeStruct((batch, bucket), jnp.float32)).compile()

        def run(x):
            order.append(int(np.asarray(x)[0, 0]))
            return fn(x)
        return run

    def pad_slice(request, chunk):
        return (request[0],)

    def gather(rows, chunks, m):
        return rows[0]

    dso = CoalescingOrchestrator(
        build, buckets=[4], pad_slice_fn=pad_slice, gather_fn=gather,
        policy=CoalescePolicy(enabled=True, max_batch=1, window_s=0.0),
        n_streams=1)
    base = 1000.0   # far-future absolute deadlines: order decided by value
    plan = [  # (tag, deadline, m-for-SRW)
        (0, base + 0.30, 4), (1, base + 0.10, 4), (2, None, 4),
        (3, base + 0.20, 4), (4, base + 0.10, 3), (5, None, 3),
    ]
    cond = dso._cond[(dso._DEFAULT_KIND, 4)]
    futs = []
    with cond:        # workers can't pop until we release the condition
        for tag, dl, m in plan:
            x = np.full((1, 4), float(tag), np.float32)
            futs.append(dso.submit((x,), m, deadline=dl))
    for f in futs:
        f.result()
    dso.shutdown()
    # EDF: 4 (dl .10, SRW 3) before 1 (dl .10, SRW 4), then .20, .30;
    # deadline-less last, SRW-ordered (5 before 2)
    assert order == [4, 1, 3, 0, 5, 2]


def test_serve_metrics_counters():
    m = ServeMetrics()
    threads = [threading.Thread(target=lambda: [m.incr("deadline_misses")
                                                for _ in range(50)])
               for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert m.summary()["deadline_misses"] == 200


def test_engine_deadline_miss_accounting(climber_setup):
    cfg, bundle, params = climber_setup
    eng = _flame(bundle, params, pack_tails=True, deadline_s=100.0)
    rng = np.random.default_rng(0)
    hist = rng.integers(0, 1000, 64).astype(np.int32)
    for _ in range(3):   # generous engine default: everything meets it
        eng.serve(hist, rng.integers(0, 1000, 12).astype(np.int32),
                  user_id=1)
    m = eng.metrics()
    assert m.get("deadline_met", 0) == 3 and "deadline_misses" not in m
    # per-request override: a 1ns budget that is still live at admission
    # (arrival stamped slightly in the future, so the admission check
    # passes deterministically) must be MISSED by the worker
    fut = eng.submit(ServeRequest(
        history=hist, candidates=rng.integers(0, 1000, 12).astype(np.int32),
        user_id=1, deadline_s=1e-9,
        arrival_t=time.perf_counter() + 5e-4))
    fut.result(timeout=60)
    assert eng.metrics()["deadline_misses"] == 1
    # a budget already exhausted when submit() runs is SHED at admission:
    # no executor work, no ResponseFuture, a dedicated counter
    with pytest.raises(DeadlineExceeded):
        eng.submit(ServeRequest(
            history=hist,
            candidates=rng.integers(0, 1000, 12).astype(np.int32),
            user_id=1, deadline_s=1e-9,
            arrival_t=time.perf_counter() - 1.0))
    m = eng.metrics()
    assert m["deadline_shed"] == 1
    assert m["deadline_misses"] == 1    # shedding is not a miss
    eng.shutdown()


# ---------------------------------------------------------------------------
# 3. model-level packing parity (bitwise, per impl)
# ---------------------------------------------------------------------------

RAGGED_LAYOUTS = [
    # (m_total, segments as (count, user)) — incl. 1-candidate segments
    (1, ((1, 0),)),
    (7, ((3, 0), (4, 2))),
    (16, ((1, 1), (1, 0), (14, 2))),
    (16, ((5, 0), (11, 1))),
]


@pytest.mark.parametrize("impl", ["reference", "chunked", "fused"])
def test_packed_scoring_bitwise_vs_unpacked(climber_setup, impl):
    """score_candidates over a segment-packed row == the same candidates
    scored on unpacked per-user rows, for every impl.

    reference/chunked are BITWISE: the packed segment attention mirrors
    the reference op sequence with identical reduction lengths, and masked
    co-segment positions contribute exact zeros.  The fused jnp path is
    gated at a tight tolerance instead: its per-candidate gathered einsum
    contracts the same dot products but XLA may reassociate the head-dim
    reduction differently than the shared-history GEMM (low-bit only;
    engine-level packed-vs-unpacked rides the same cross-executable
    tolerance every other A/B in this repo uses)."""
    cfg, bundle, params = climber_setup
    rng = np.random.default_rng(3)
    n_hist = 64
    kvs = []
    for u in range(3):
        batch = {"history": jnp.asarray(
            rng.integers(0, 10_000, (1, n_hist)).astype(np.int32)),
            "side": jnp.asarray(rng.standard_normal((1, 12)), jnp.float32)}
        kvs.append(bundle.encode_history(params, batch, impl="chunked"))
    kv_stack = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *kvs)

    for m_total, segments in RAGGED_LAYOUTS:
        cand = rng.integers(0, 10_000, (1, m_total)).astype(np.int32)
        seg = np.zeros((1, m_total), np.int32)
        off = 0
        for count, user in segments:
            seg[0, off:off + count] = user
            off += count
        assert off == m_total
        packed = np.asarray(bundle.score_candidates(
            params, kv_stack, jnp.asarray(cand), impl=impl,
            row_index=jnp.asarray(seg)))
        off = 0
        for count, user in segments:
            unpacked = np.asarray(bundle.score_candidates(
                params, kvs[user], jnp.asarray(cand), impl=impl))
            a, b = packed[0, off:off + count], unpacked[0, off:off + count]
            if impl == "fused":
                np.testing.assert_allclose(
                    a, b, atol=1e-3, rtol=0,
                    err_msg=f"impl={impl} layout={segments} segment@{off}")
            else:
                np.testing.assert_array_equal(
                    a, b,
                    err_msg=f"impl={impl} layout={segments} segment@{off}")
            off += count


def test_packed_extend_index_rejected(climber_setup):
    """Suffix extension is causal — the per-candidate seg index must be
    rejected, not silently mis-scored."""
    from repro.core import sumi
    k = jax.random.normal(jax.random.key(0), (1, 4, 2, 16))
    with pytest.raises(ValueError, match="causal"):
        sumi.extend_attention(k, k, k, k, k, impl="chunked",
                              row_index=jnp.zeros((1, 4), jnp.int32))


# ---------------------------------------------------------------------------
# 4. engine level
# ---------------------------------------------------------------------------

def _ragged_requests(n, seed=5, n_users=4, n_hist=64):
    tc = TrafficConfig(candidate_counts=(3, 7, 19, 33),
                       distribution="jittered", n_requests=n,
                       n_history=n_hist, seed=seed, n_users=n_users)
    reqs = generate_traffic(tc, n_items=10_000)
    rng = np.random.default_rng(seed + 1)
    for u in range(2):   # M=1 rides along (the hardest ragged case)
        reqs.append({"history": reqs[u]["history"],
                     "user_id": reqs[u]["user_id"],
                     "candidates": rng.integers(0, 10_000, 1)
                     .astype(np.int32)})
    return reqs


@pytest.mark.parametrize("impl", ["chunked", "fused"])
def test_packed_engine_concurrent_bitwise_matches_sequential(climber_setup,
                                                             impl):
    """The tentpole contract: concurrent packed serving (segments of many
    requests sharing rows at arbitrary offsets) is bitwise-identical to
    the same engine serving sequentially — one executable, and segment
    placement is bitwise-invariant."""
    cfg, bundle, params = climber_setup
    eng = _flame(bundle, params, pack_tails=True, impl=impl)
    reqs = _ragged_requests(14)
    for r in reqs[:6]:   # warm the pool: hot-hit steady state
        eng.serve(r["history"], r["candidates"], user_id=r.get("user_id"))
    sequential = [eng.serve(r["history"], r["candidates"],
                            user_id=r.get("user_id")) for r in reqs]
    concurrent = run_workload_async(eng, reqs)["outputs"]
    for s, c in zip(sequential, concurrent):
        np.testing.assert_array_equal(s, c)
    m = eng.metrics()
    assert m["dso_packed_segments"] > 0
    eng.shutdown()


def test_packed_engine_matches_unpacked_and_reclaims_padding(climber_setup):
    """Packed vs unpacked engines: scores agree at the cross-AOT-executable
    tolerance (different XLA fusions; bitwise is asserted within one
    executable above and at the model level), and the packed side
    dispatches measurably less candidate padding."""
    cfg, bundle, params = climber_setup
    reqs = _ragged_requests(16)
    outs, engines = {}, {}
    for pack in (False, True):
        eng = _flame(bundle, params, pack_tails=pack, impl="fused")
        for r in reqs[:6]:
            eng.serve(r["history"], r["candidates"],
                      user_id=r.get("user_id"))
        outs[pack] = run_workload_async(eng, reqs)["outputs"]
        engines[pack] = eng
    for a, b in zip(outs[False], outs[True]):
        np.testing.assert_allclose(a, b, atol=2e-3, rtol=0)
    pf_un = engines[False].metrics()["dso_padded_fraction"]
    pf_pk = engines[True].metrics()["dso_padded_fraction"]
    m = engines[True].metrics()
    assert m["dso_packed_segments"] > 0 and m["dso_packed_rows"] > 0
    assert pf_pk < pf_un, (pf_pk, pf_un)
    # the padded-fraction gauge surfaces through ServeMetrics; the
    # queue delay is the DSO's own mean
    assert "padded_fraction" in m and "dso_queue_delay_ms" in m
    for eng in engines.values():
        eng.shutdown()


def test_packing_halves_padding_on_zipf_tails(climber_setup):
    """Zipf slates of 3-15 candidates against one 32-slot bucket make
    nearly every request one padded tail chunk.  Packing fills shared rows
    with the tails of many requests: on a warm pool the packed engine's
    cached dispatches carry at most half the unpacked engine's padded
    fraction, and its scores agree at the cross-executable tolerance."""
    cfg, bundle, params = climber_setup
    tc = TrafficConfig(candidate_counts=(3, 5, 9, 15), distribution="zipf",
                       n_requests=32, n_history=64, seed=31, n_users=8)
    reqs = generate_traffic(tc, n_items=10_000)
    outs, padded = {}, {}
    for pack in (False, True):
        eng = _flame(bundle, params, buckets=(32,), n_streams=1,
                     max_batch=8, n_workers=8, window_s=0.05,
                     pack_tails=pack, impl="chunked")
        try:
            run_workload_async(eng, reqs)          # pool every user
            m0 = eng.metrics()
            outs[pack] = run_workload_async(eng, reqs)["outputs"]
            m1 = eng.metrics()
        finally:
            eng.shutdown()
        slots, valid = (m1[k] - m0[k] for k in ("dso_cand_slots_cached",
                                                "dso_cand_valid_cached"))
        padded[pack] = 1.0 - valid / slots
    assert padded[False] >= 2 * padded[True], padded
    for a, b in zip(outs[False], outs[True]):
        np.testing.assert_allclose(a, b, atol=2e-3, rtol=0)


def test_pack_tails_requires_history_cache(climber_setup):
    cfg, bundle, params = climber_setup
    with pytest.raises(ValueError, match="history_cache"):
        _flame(bundle, params, history_cache=False, pack_tails=True)


# ---------------------------------------------------------------------------
# 5. quantized extend basis (raw, no host dequant)
# ---------------------------------------------------------------------------

def test_pool_raw_basis_returns_stored_representation(climber_setup):
    cfg, bundle, params = climber_setup
    pool = HistoryKVPool(4, dtype="int8")
    kv = {"b0": {"k": np.ones((1, 2, 5, 2, 16), np.float32)}}
    pool.put("u", "fp0", kv, hist_window=np.arange(5))
    _, status, basis = pool.lookup("u", "fp-new", want_basis=True,
                                   raw_basis=True)
    assert status == "stale"
    leaf = basis.kv["b0"]["k"]
    assert isinstance(leaf, tuple)
    values, scale = leaf
    assert values.dtype == np.int8 and scale.dtype == np.float32


def test_extend_history_raw_basis_bitwise(climber_setup):
    """extend_history over a RAW (stored int8) basis == the same extension
    over the host-dequantized basis, bit for bit — the in-graph dequant is
    the same formula as the pool's dequantize_leaf."""
    cfg, bundle, params = climber_setup
    rng = np.random.default_rng(11)
    n = 64
    batch = {"history": jnp.asarray(
        rng.integers(0, 10_000, (1, n)).astype(np.int32)),
        "side": jnp.asarray(rng.standard_normal((1, 12)), jnp.float32)}
    kv = bundle.encode_history(params, batch, impl="chunked")
    payload, _ = quantize_kv(jax.tree.map(np.asarray, kv), "int8")
    for impl in ("chunked", "fused"):
        out_raw = bundle.extend_history(params, raw_kv_view(payload), batch,
                                        prefix_len=n, impl=impl)
        out_deq = bundle.extend_history(params, dequantize_kv(payload),
                                        batch, prefix_len=n, impl=impl)
        for a, b in zip(jax.tree.leaves(out_raw), jax.tree.leaves(out_deq)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_incremental_engine_extends_from_raw_basis(climber_setup):
    """End to end: the fused int8 engine serves tail-append (stale) traffic
    through the extend family compiled against raw pool specs."""
    cfg, bundle, params = climber_setup
    eng = _flame(bundle, params, pack_tails=True, impl="fused",
                 pool_dtype="int8", incremental_history=True)
    rng = np.random.default_rng(2)
    hists = {u: rng.integers(0, 10_000, 80).astype(np.int32)
             for u in range(3)}
    outs = []
    for _ in range(3):
        for u in range(3):
            hists[u] = np.concatenate(
                [hists[u], rng.integers(0, 10_000, 4).astype(np.int32)])
            outs.append(eng.serve(
                hists[u], rng.integers(0, 10_000, 9).astype(np.int32),
                user_id=u))
    m = eng.metrics()
    assert m["pool_extensions"] > 0
    assert all(np.isfinite(o).all() for o in outs)
    eng.shutdown()


# ---------------------------------------------------------------------------
# 6. per-row dispatch: in-graph stacking and splitting
# ---------------------------------------------------------------------------

def test_per_row_signature_stacks_and_splits_in_graph():
    """The executor half of the contract: B slot groups of [1, ...] KV rows
    concatenate in graph to the stacked operand, the host rest passes
    through, and a split executor returns B per-row outputs."""
    def fn(params, k, v, x):
        return {"y": k * params + v, "z": x}
    B = 3
    shapes = (jax.ShapeDtypeStruct((B, 2, 4), jnp.float32),
              jax.ShapeDtypeStruct((B, 2, 4), jnp.float32),
              jax.ShapeDtypeStruct((B, 5), jnp.int32))
    same, same_shapes = per_row_signature(fn, shapes, 0, split=False)
    assert same is fn and same_shapes == shapes
    wrapped, row_shapes = per_row_signature(fn, shapes, 2, split=True)
    assert [s.shape for s in row_shapes] == [(1, 2, 4)] * 2 * B + [(B, 5)]
    rng = np.random.default_rng(0)
    ks = [rng.standard_normal((1, 2, 4)).astype(np.float32)
          for _ in range(B)]
    vs = [rng.standard_normal((1, 2, 4)).astype(np.float32)
          for _ in range(B)]
    x = rng.integers(0, 9, (B, 5)).astype(np.int32)
    ex = jax.jit(wrapped).lower(2.0, *row_shapes).compile()
    out = ex(2.0, *[a for kv in zip(ks, vs) for a in kv], x)
    ref = jax.jit(fn)(2.0, np.concatenate(ks), np.concatenate(vs), x)
    assert isinstance(out, tuple) and len(out) == B
    for i, row in enumerate(out):
        assert row["y"].shape == (1, 2, 4) and row["z"].shape == (1, 5)
        np.testing.assert_array_equal(np.asarray(row["y"]),
                                      np.asarray(ref["y"])[i:i + 1])
        np.testing.assert_array_equal(np.asarray(row["z"]), x[i:i + 1])


def test_orchestrator_hands_kv_rows_per_slot():
    """A ``mode="row"`` family: ``build_fn`` gets the orchestrator's own
    per-row signature, co-riders' KV rows reach the executor per slot,
    unused slots repeat slot 0's objects, the split output is handed out by
    index, and every dispatch counts as in-graph."""
    B, seen = 4, []

    def build(kind, bucket, batch, signature):
        def fn(params, kv, x):
            return kv.sum(axis=(1, 2))[:, None] + x
        wrapped, shapes = signature(
            fn, (jax.ShapeDtypeStruct((batch, 3, 2), jnp.float32),
                 jax.ShapeDtypeStruct((batch, bucket), jnp.float32)))
        ex = jax.jit(wrapped).lower(0.0, *shapes).compile()

        def run(*args):
            seen.append(args)
            return ex(0.0, *args)
        return run

    dso = CoalescingOrchestrator(
        build, pad_slice_fn=lambda req, c, kind: req,
        gather_fn=lambda rows, cs, m, kind: rows[0],
        policy=CoalescePolicy(enabled=True, max_batch=B, window_s=0.05),
        n_streams=1, families={"k": [4]}, kv_kinds={"k": (1, "row")},
        device_output_kinds=("k",))
    rng = np.random.default_rng(1)
    reqs = [(rng.standard_normal((1, 3, 2)).astype(np.float32),
             rng.standard_normal((1, 4)).astype(np.float32))
            for _ in range(2)]
    with dso._cond[("k", 4)]:     # both chunks ride one dispatch
        futs = [dso.submit(r, 4, kind="k") for r in reqs]
    outs = [f.result() for f in futs]
    st = dso.stats()
    dso.shutdown()
    assert st["dispatches"] == st["ingraph_dispatches"] == len(seen) == 1
    args = seen[0]
    assert len(args) == B + 1
    assert args[0] is reqs[0][0] and args[1] is reqs[1][0]
    assert args[2] is reqs[0][0] and args[3] is reqs[0][0]
    for (kv, x), out in zip(reqs, outs):
        assert out.shape == (1, 4)
        np.testing.assert_allclose(np.asarray(out),
                                   kv.sum(axis=(1, 2))[:, None] + x,
                                   rtol=1e-6)


def test_packed_dispatch_counts_qblocks_by_the_kernel_rule():
    """``qblocks_uniform`` / ``qblocks_mixed`` count a packed dispatch's
    q blocks as the pointwise kernel blocks them (``fs_ops.qblock_counts``):
    one full 256-candidate chunk fills two uniform 128-row blocks; two
    tails sharing a 32-slot row (8-aligned) make one mixed block."""
    from repro.kernels.fused_score import ops as fs_ops

    seen = []

    def build(kind, bucket, batch, signature):
        def run(*args):
            seen.append(np.asarray(args[-2]))            # the seg index
            return np.zeros(args[-1].shape, np.float32)
        return run

    dso = CoalescingOrchestrator(
        build, pad_slice_fn=lambda req, c, kind: (
            req[0], req[1][:, c.start:c.start + c.valid]),
        gather_fn=lambda rows, cs, m, kind: rows[0],
        policy=CoalescePolicy(enabled=True, max_batch=4, window_s=0.05,
                              pack_rows=1, pack_align=fs_ops.PACK_ALIGN),
        n_streams=1, families={"k": [256, 32]},
        kv_kinds={"k": (1, "packed")})
    kv = [np.full((1, 2), u, np.float32) for u in range(3)]
    try:
        dso.submit((kv[0], np.ones((1, 256), np.int32)), 256, kind="k",
                   dedup_token=0).result()
        full = dso.stats()
        with dso._cond[("k", 32)]:     # both tails ride one dispatch
            futs = [dso.submit((kv[u], np.ones((1, 5), np.int32)), 5,
                               kind="k", dedup_token=u) for u in (1, 2)]
        for f in futs:
            f.result()
        st = dso.stats()
    finally:
        dso.shutdown()
    assert (full["qblocks_uniform"], full["qblocks_mixed"]) == (2, 0)
    assert (st["qblocks_uniform"], st["qblocks_mixed"]) == (2, 1)
    assert st["dispatches"] == len(seen) == 2
    want = [fs_ops.qblock_counts(seg, 8) for seg in seen]
    assert tuple(map(sum, zip(*want))) == (2, 1)


def _session_steps(seed=7, n_users=4, steps=3):
    """Fixed mixed session: one request per user a step; step 0 misses
    (encode), step 1 hits, step 2 grows even users' histories (extend)
    while odd users hit again.  Slates span one and two chunks."""
    rng = np.random.default_rng(seed)
    hists = {u: rng.integers(0, 10_000, 80).astype(np.int32)
             for u in range(n_users)}
    out = []
    for step in range(steps):
        wave = []
        for u in range(n_users):
            if step == 2 and u % 2 == 0:
                hists[u] = np.concatenate(
                    [hists[u], rng.integers(0, 10_000, 3).astype(np.int32)])
            m = int(rng.integers(1, 48))
            wave.append((hists[u].copy(),
                         rng.integers(0, 10_000, m).astype(np.int32), u))
        out.append(wave)
    return out


def _fused_int8(bundle, params):
    return _flame(bundle, params, pack_tails=True, impl="fused",
                  pool_dtype="int8", incremental_history=True)


class _NoJnp:
    """Stand-in for the DSO module's ``jnp``: any use fails (and is
    recorded, in case a dispatch thread swallows the error)."""

    def __init__(self):
        self.touched = []

    def __getattr__(self, name):
        self.touched.append(name)
        raise AssertionError(f"eager jnp.{name} on the DSO dispatch path")


@pytest.fixture(scope="module")
def ingraph_session(climber_setup):
    """One fused int8 packed engine serving the fixed session one request
    at a time, with every executor call recorded (kind, args, output) and
    the DSO's ``jnp`` replaced by a sentinel while it serves."""
    cfg, bundle, params = climber_setup
    eng = _fused_int8(bundle, params)
    kind_of = {id(ex): kind for (kind, _), ex in eng.dso.compiled.items()}
    calls = []
    real = engine_mod._ParamsBound.__call__

    def record(self, *args):
        out = real(self, *args)
        calls.append((kind_of[id(self)], args, out))
        return out

    no_jnp = _NoJnp()
    m0 = eng.metrics()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine_mod._ParamsBound, "__call__", record)
        mp.setattr(dso_mod, "jnp", no_jnp)
        outs = [np.asarray(eng.serve(h, c, user_id=u))
                for wave in _session_steps() for h, c, u in wave]
    m1 = eng.metrics()
    yield {"eng": eng, "calls": calls, "no_jnp": no_jnp, "outs": outs,
           "delta": {k: v - m0.get(k, 0) for k, v in m1.items()
                     if isinstance(v, (int, float))}}
    eng.shutdown()


def test_ingraph_mixed_session_concurrent_bitwise_matches_solo(
        climber_setup, ingraph_session):
    """Fused impl, int8 pool, packed tails: a session of hits, misses and
    extends served a wave at a time concurrently (co-riders share
    dispatches) scores bitwise equal to the same session served one request
    at a time."""
    cfg, bundle, params = climber_setup
    eng = _fused_int8(bundle, params)
    concurrent = []
    for wave in _session_steps():
        futs = [eng.submit(ServeRequest(history=h, candidates=c, user_id=u))
                for h, c, u in wave]
        concurrent += [np.asarray(f.result(timeout=120).output)
                       for f in futs]
    m = eng.metrics()
    eng.shutdown()
    assert m["pool_hits"] > 0 and m["pool_misses"] > 0
    assert m["pool_extensions"] > 0
    solo = ingraph_session["outs"]
    assert len(solo) == len(concurrent)
    for s, c in zip(solo, concurrent):
        np.testing.assert_array_equal(s, c)


def test_dispatch_is_one_launch_without_eager_jnp(ingraph_session):
    """Every dispatch of the session makes exactly one executor call, and
    the dispatch path never touches the DSO module's ``jnp``."""
    calls, d = ingraph_session["calls"], ingraph_session["delta"]
    assert d["dso_dispatches"] > 0
    assert len(calls) == d["dso_dispatches"]
    assert {k for k, _, _ in calls} == {"encode", "extend", "cached"}
    assert ingraph_session["no_jnp"].touched == []
    assert all(np.isfinite(o).all() for o in ingraph_session["outs"])


def test_cached_dispatch_receives_pool_arrays(ingraph_session):
    """A pool hit's ``cached`` dispatch gets the pool's stored arrays
    themselves as its KV arguments, and its padding slots repeat them."""
    eng = ingraph_session["eng"]
    n_kv = eng.dso._kv_rows["cached"]
    B = eng.dso.policy.batch
    cached = [args for k, args, _ in ingraph_session["calls"]
              if k == "cached"]
    for u in (1, 3):       # odd users: entries untouched since step 0
        stored = _stored_arrays(
            eng.history_pool._entries[("u", u)].payload)
        assert len(stored) == n_kv
        hits = [args for args in cached
                if all(a is b for a, b in zip(args[:n_kv], stored))]
        assert hits, f"no cached dispatch got user {u}'s stored arrays"
        for args in hits:
            assert all(args[j] is args[j % n_kv] for j in range(B * n_kv))


def test_encode_extend_rows_reach_pool_split(ingraph_session):
    """``encode`` / ``extend`` executors return B per-row outputs; the pool
    holds separate [1, ...] arrays (no view into a stacked parent), and
    ``pool_bytes_used`` is the entries' stored bytes exactly."""
    eng = ingraph_session["eng"]
    B = eng.dso.policy.batch
    for kind, _, out in ingraph_session["calls"]:
        if kind in ("encode", "extend"):
            assert isinstance(out, tuple) and len(out) == B
            assert all(a.shape[0] == 1 for row in out
                       for a in jax.tree.leaves(row))
    entries = list(eng.history_pool._entries.values())
    assert len(entries) == 4
    for e in entries:
        for a in _stored_arrays(e.payload):
            assert a.shape[0] == 1
            base = getattr(a, "base", None)
            while isinstance(base, np.ndarray):
                assert base.nbytes == a.nbytes, "a view into a stacked row"
                base = base.base
    per_entry = sum(int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
                    for s in eng._cached_row_specs)
    assert eng.metrics()["pool_bytes_used"] == len(entries) * per_entry


def test_ingraph_dispatch_counter_counts_non_encode(ingraph_session):
    """``dso_ingraph_dispatches`` counts exactly the dispatches whose KV
    rows went per slot: every dispatch but ``encode``."""
    d = ingraph_session["delta"]
    assert d["dso_dispatches_encode"] > 0
    assert d["dso_ingraph_dispatches"] > 0
    assert d["dso_ingraph_dispatches"] == \
        d["dso_dispatches"] - d["dso_dispatches_encode"]


def test_kv_kinds_reject_unknown_slot_mode():
    """``kv_kinds`` modes are one of row / dedup / packed; anything else is
    refused when the orchestrator is built, before any executor compiles."""
    built = []
    with pytest.raises(ValueError, match="KV slot mode"):
        CoalescingOrchestrator(
            lambda kind, b, batch, sig: built.append(kind),
            pad_slice_fn=lambda req, c, kind: req,
            gather_fn=lambda rows, cs, m, kind: rows[0],
            families={"k": [4]}, kv_kinds={"k": (1, "shared")})
    assert built == []


def test_pool_put_runs_in_dispatch_order(climber_setup):
    """A fresh entry's pool put (the int8 quantize and layout publish of a
    device-resident encode output) runs under the DSO's dispatch lock, the
    lock multi-device launches hold: an eager multi-device op racing a
    dispatch deadlocks a host mesh."""
    cfg, bundle, params = climber_setup
    eng = _flame(bundle, params, impl="chunked", pool_dtype="int8")
    eng.dso._dispatch_lock = lock = threading.Lock()  # as a mesh sets it
    held = []
    real_put = eng.history_pool.put

    def put(*args, **kwargs):
        held.append(lock.locked())
        return real_put(*args, **kwargs)

    eng.history_pool.put = put
    rng = np.random.default_rng(5)
    try:
        for u in range(2):
            out = eng.serve(rng.integers(0, 10_000, 64).astype(np.int32),
                            rng.integers(0, 10_000, 9).astype(np.int32),
                            user_id=u)
            assert np.isfinite(np.asarray(out)).all()
    finally:
        eng.shutdown()
    assert held == [True, True]
    assert not lock.locked()
