"""Serving API v2 benchmark — dispatch + history-cache A/Bs.

Profile 1 (mixed traffic): coalesced vs per-request dispatch.  Drives
concurrent jittered traffic (non-bucket-aligned candidate counts, the
DSO's hard case) through two FlameEngine configurations that differ only
in the coalescing policy:

  uncoalesced   executors (1, bucket); every chunk dispatches alone
  coalesced     executors (max_batch, bucket); same-bucket chunks from
                different in-flight requests share one dispatch

Profile 2 (repeat-user / session re-rank): history-KV pool on vs off.
A fixed population of users each re-ranks several fresh candidate slates
against a stable history — the MTServe regime.  With the pool on, scoring
runs candidate-only executors against cached per-layer history K/V
(O(M) tokens instead of O(n_history + M) per block); misses pay one
batched encode.  Measured at steady state (pool warmed by a first sweep).

Profile 3 (PDA v2 hot path): the PR 2-style pool (host-resident entries,
KV rows restacked once per chunk) vs PDA v2 (device-resident entries +
KV-row dedup in the dispatcher) on the same repeat-user workload — the
"device-resident pool entries" ROADMAP item, isolated.

Profile 4 (suffix extension): stale-sweep workload — every user's history
tail-appends between sweeps, so every request is a stale hit.  Full
re-encode (incremental off) vs incremental suffix extension (re-encode one
token per block against the cached prefix).  Same seed on both sides, so
outputs are compared pairwise at the pool tolerance.

Profile 5 (quantized pool): int8 pool entries vs native on the hot
repeat-user path — bytes/entry ratio (users-per-replica capacity) and the
measured score drift.

Profile 6 (fke): the fused candidate-scoring engine (``impl="fused"``,
kernels/fused_score) vs the framework-composed ``impl="chunked"`` on the
repeat-user workload over a quantized (int8) pool — the paper-scale FKE
configuration.  The fused executors read the pool's stored int8 rows and
the dedup row index in-kernel, so a hit skips the host dequantize AND the
``kv[idx]`` materialization; KV-row dedup auto-enables even on the CPU
backend because the gather is free.  Run standalone with
``python -m benchmarks.bench_serving --profile fke`` (the CI gate).

Profile 7 (dso_nonuniform): DSO v2 segment-packed ragged dispatch vs the
PR-4 coalescing dispatcher under non-uniform candidate traffic (zipf +
lognormal over tiny counts — nearly every request is one partial tail
chunk).  The packed engine fills shared rows with candidate segments from
many requests (each steered to its own user's pooled KV by the per-
candidate seg index), so ``padded_fraction`` collapses and items/s rises
with no score change beyond the cross-executable tolerance.  Run
standalone with ``--profile dso_nonuniform`` (a CI gate).

Profile 8 (sharded): mesh-sharded serving (data=2, model=2) vs
single-device on the repeat-user workload, A/B-interleaved inside a
subprocess whose host platform is forced to 4 devices (XLA_FLAGS must be
set before jax imports, so the parent cannot host the mesh itself).
Records the per-shard pool byte split; the throughput gate is a PARITY
floor, not a speedup — emulated devices time-slice one CPU and
multi-device dispatches serialize.  Run standalone with
``--profile sharded`` (a CI gate).

Profile 9 (decode): generative candidate decode (ISSUE 8) — DSO-packed
beam rows (``pack_tails=True``) vs per-request decode dispatch on zipf
repeat-user traffic with alternating top-k and beam requests over tiny
token universes.  Each autoregressive step scores every beam's token
universe against pooled history KV; the packed side merges beam segments
from many in-flight requests into shared executor rows.  Sequences must
match bitwise across the two engines (same AOT executables, row-wise
batch-invariant) and the gen-tokens/s gate is cpu-count-aware: speedup
on multi-core, parity floor on a single core.  Run standalone with
``--profile decode`` (a CI gate).

Profile 10 (overload): SLO-tiered EDF admission + load shedding vs the
PR-1 FIFO discipline under sustained overload (every request submitted at
once against a small worker pool), gated on interactive-tier
goodput-under-SLO (median per-round, cpu-count-aware floor); plus a chaos
pass under deterministic fault injection (transient dispatch failures,
worker stalls, pool eviction storms) gated on ZERO hung futures — every
submission resolves, result or error.  Run standalone with
``--profile overload`` (a CI gate).

All profiles run against a warmed PDA cache (hot steady state) so the
measurement reflects dispatch economics, not feature-fetch cost.

Correctness gates before any throughput claim:
  1. coalesced concurrent scores are bitwise-identical to the same engine
     serving the same requests sequentially (same executable — guaranteed
     by per-row independence, hard assert);
  2. coalesced scores are bitwise-identical to the uncoalesced baseline
     (cross-executable; holds for this config and asserted so a future
     XLA codegen change fails loudly rather than silently);
  3. pooled-history scores match the full-pass engine at tight tolerance
     (the split forward is mathematically exact; the two AOT executables
     fuse differently, so isolated bf16 lanes may round differently —
     the gate admits <= 2e-3 absolute on sigmoid outputs, ~half a bf16
     ulp at 0.5, and reports the bitwise-identical request fraction);
  4. suffix-extension scores match the full re-encode run at the same
     tolerance, and int8 pool drift stays under its stated bound (5e-2).

Perf gates (explicit, enforced on every run): pool >= 1.5x full pass;
suffix extension >= 1.1x full re-encode on the stale-sweep profile;
FKE >= 1.3x chunked on the int8 repeat-user profile (with nonzero
dedup_rows_saved on the fused side — the CPU backend included);
PDA v2 >= 0.9x the v1-style pool.  The last one is a parity guard, not a
victory lap: on the CPU backend "device" and "host" placement are the same
memory, so the v2 machinery must simply cost nothing — its wins
(HBM-resident entries skipping the per-dispatch H2D copy, dedup skipping
one transfer per duplicate row) are transfer-bound and materialize on
accelerator backends, where kv_dedup auto-enables.  The forced-dedup row
records the dedup machinery live (rows saved -> modeled transfer bytes).

Emits ``BENCH_serving.json`` at the repo root so future PRs have a perf
trajectory to compare against (see benchmarks/README.md for every field).
"""
from __future__ import annotations

import argparse
import json
import os

import jax
import numpy as np

from benchmarks.common import make_climber
from repro.core.pda import RemoteFeatureStore
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import create_engine
from repro.serving.scheduler import (TrafficConfig, generate_traffic,
                                     run_workload_async)

HISTORY = 64
COUNTS = (16, 32, 64)
N_REQUESTS = 64
N_ITEMS = 5_000
BUCKETS = (32, 16)
MAX_BATCH = 4
N_WORKERS = 8
# repeat-user profile: longer history (the term the pool amortizes away),
# multi-chunk candidate counts (the regime where KV-row dedup bites: a
# m=96 request splits into three bucket-32 chunks that share one KV row),
# and a deeper batch axis so co-batched same-user rows dedup too
REPEAT_HISTORY = 128
REPEAT_USERS = 8
REPEAT_COUNTS = (48, 64, 96)
REPEAT_MAX_BATCH = 8
POOL_SLOTS = 32
# stale-sweep profile: longer history still, so the full re-encode the
# extension path avoids dominates dispatch overhead even at bench scale
STALE_HISTORY = 256
# fke profile: paper-scale FKE configuration — int8 pool (the capacity
# setting), history long enough that cached scoring (not dispatch) is the
# cost, multi-chunk candidate counts so the dedup row index engages.
# Fewer pipeline workers than the other profiles: the gate is a wall-clock
# ratio, and 8 workers on a 2-core CI box drown it in scheduler noise
FKE_HISTORY = 512
FKE_WORKERS = 4
FKE_ROUNDS = 5
# The fused engine's wall-clock win comes from work it REMOVES per dispatch
# (host dequantize, the kv[idx] restack) — savings that overlap with other
# requests' compute only when there is more than one core to overlap on.
# On a single-core box every engine serializes onto the same core and the
# fused path's margin collapses into scheduler noise, so the gate degrades
# to a PARITY floor there: fused must not be slower, but is not required to
# win.  Multi-core boxes keep the 1.3x gate (measured 1.5-1.8x on 2 cores).
FKE_SPEEDUP_MIN = 1.3 if (os.cpu_count() or 1) > 1 else 1.0
FKE_TOL = 1e-2      # chunked dequantizes, fused folds the scale in-kernel:
                    # same stored rows, reassociated math (~3e-3 measured)
# dso_nonuniform profile: DSO v2 segment packing vs PR-4 coalescing under
# non-uniform candidate traffic (paper Fig 10 / Table 5's regime).  Counts
# are tiny and skewed (zipf mostly draws the smallest; lognormal is the
# heavy-tailed continuous variant) against a single 32-bucket, so nearly
# every request is ONE partial tail chunk padded up to its covering bucket
# (padded_fraction ~0.7-0.8) — the packer fills shared rows with segments
# from many requests instead, and pack_rows (max_batch/4 = 2) compiles a
# quarter of the unpacked row capacity: the same chunk fill rides a (2,
# 32) executor instead of an (8, 32) one.  Users <= the batch axis so one
# packed dispatch can stack every user's KV; one stream per bucket so a
# single collector sees the whole pending queue.
DSO_HISTORY = 192
DSO_BUCKETS = (32,)
DSO_COUNTS = (3, 5, 9, 15)
DSO_STREAMS = 1
DSO_ROUNDS = 7
DSO_SPEEDUP_MIN = 1.2   # packed >= 1.2x items/s (median per-round, zipf)
DSO_PAD_RATIO_MIN = 2.0  # unpacked padded_fraction >= 2x the packed one
DSO_TOL = 2e-3           # cross-AOT-executable tolerance (see profile 2)
# the v2 engine carries an explicit byte budget (active accounting; sized
# far above the working set so the hot path is budget-checked, not evicted)
V2_BUDGET_BYTES = 64 << 20
# sharded profile: mesh-sharded serving vs single-device on the repeat-user
# workload, run in a subprocess with XLA's host platform forced to 4
# devices (the flag must be set before jax imports, so the parent process
# cannot host the mesh itself).  The mesh is (data=2, model=2): the request
# batch splits over "data" and the KV heads split over "model", so each
# shard holds half the pool bytes (the per-shard budget) — recorded from
# the pool_bytes_used_shard{i} gauges.  The gate is a PARITY floor, not a
# speedup: all 4 "devices" are slices of the same CPU, so sharding buys no
# cycles here and the host collectives cost real time — the floor asserts
# the mesh machinery (sharded executors, per-shard pool, coalesced global
# batch) doesn't tax the hot path beyond CPU-emulation overhead.  Real
# wins (N× KV-head bandwidth, N× pool capacity) need N physical devices.
# The emulation overhead is real and stable: emulated devices time-slice
# one CPU's cores, per-layer TP collectives run through XLA's in-process
# rendezvous, and multi-device dispatches serialize (see
# CoalescingOrchestrator.serialize_dispatch) — measured x0.31-0.34 per
# round.  The 0.2 floor catches pathological regressions (a reshard per
# dispatch, a pool republish per hit) that land far below it, without
# flaking on scheduler noise.
# Tolerance: the TP out-projection all-reduce reassociates sums through
# the block stack (~1e-3 observed); the bitwise criterion lives in
# tests/test_sharded_serving.py on the pure-data (4, 1) mesh, where local
# per-device shapes match single-device exactly.
SHARDED_DEVICES = 4
SHARDED_MODEL_PARALLEL = 2
SHARDED_ROUNDS = 5
SHARDED_PARITY_MIN = 0.2
SHARDED_TOL = 5e-3
# decode profile: generative beam/top-k decode, DSO-packed beam rows vs
# per-request dispatch.  Tiny zipf-skewed token universes (most requests
# decode over a handful of ids), so every decode step is one partial chunk
# per request on the unpacked side; the packer fills shared rows with beam
# segments from many in-flight requests instead.  The gate follows the FKE
# rule: packing removes per-dispatch overhead whose win needs cores to
# overlap on — a multi-core box must show the speedup, a single-core box
# must hold parity (the packer must at least pay for itself).
DECODE_HISTORY = 96
DECODE_COUNTS = (4, 6, 10, 14)
DECODE_STEPS = 5
DECODE_BEAM = 4
DECODE_ROUNDS = 5
DECODE_WORKERS = 4
DECODE_REQUESTS = 24
DECODE_SPEEDUP_MIN = 1.1 if (os.cpu_count() or 1) > 1 else 0.9
# decode_fused profile (ISSUE 10, FKE v2): fused generative decode — the
# lengths-masked fused kernel scores every decode step in one executor
# call against stored pool KV — vs the chunked per-pass formulation, both
# packed and over the same int8 pool, so the only delta is the decode
# formulation itself.  Correctness is gated on a NATIVE-pool parity pass
# (exact f32 math on both sides: sequences must match token for token);
# the timed A/B runs on int8 where the fused side's in-kernel dequant
# pays off.  Multi-core boxes must show the speedup; a single-core box
# holds parity (the fused formulation must at least pay for itself).
DECODE_FUSED_SPEEDUP_MIN = 1.2 if (os.cpu_count() or 1) > 1 else 0.9
# chaos arm shared by the decode profiles: dispatch faults retrying
# decode-step dispatches plus pool eviction storms that evict PARKED beam
# caches mid-generation — the liveness gate is zero hung futures and the
# recovery gate is gen_replays > 0 (evicted beams re-decoded from the
# root, not failed).  Storms roll per decode round (the engine fires the
# evict arm between a round's beam parks and the next round's lookups —
# the only window where an eviction can force a replay), so a modest
# probability still lands many mid-generation evictions
DECODE_FAULT_SPEC = "dispatch:0.1,evict:0.15"
# overload profile (ISSUE 9): sustained arrival rate > service rate —
# every request submits at once against a small worker pool, so the
# admission queue stays saturated and ordering policy decides who makes
# their SLO.  A/B: FIFO admission + blocking backpressure (the PR-1
# discipline) vs EDF admission + tiered shedding.  The gate is
# goodput-under-SLO on the INTERACTIVE tier (requests completing inside
# their deadline, from the goodput_interactive counter): EDF serves the
# tight-deadline work first while FIFO makes it wait behind bulk.  The
# ratio smooths +1 on both sides (rounds where FIFO strands every
# interactive request would otherwise divide by zero) and gates on the
# median per-round value.  Single-core boxes keep a reduced floor: the
# ordering win survives serialization, but one poisoned round of two
# workers time-slicing one core adds noise the multicore floor would
# flake on.
OVERLOAD_HISTORY = 96
OVERLOAD_COUNTS = (8, 16, 32)
OVERLOAD_REQUESTS = 48
OVERLOAD_ROUNDS = 5
OVERLOAD_WORKERS = 2
OVERLOAD_PENDING = 16
OVERLOAD_TIER_MIX = {"interactive": 0.3, "standard": 0.4, "bulk": 0.3}
# interactive SLO sits between EDF's interactive-clear time (~0.3x the
# full-round wall time: EDF front-runs the ~30% interactive slice) and
# FIFO's full-round wall time (~0.11 s here), so FIFO strands most
# late-arriving interactive work past deadline while EDF meets all of it
OVERLOAD_TIER_SLO = {"interactive": 0.04, "standard": 1.5, "bulk": 10.0}
OVERLOAD_GOODPUT_MIN = 1.2 if (os.cpu_count() or 1) > 1 else 1.05
# chaos arm of the overload profile: transient dispatch faults (exercising
# the DSO retry loop), worker stalls (exercising the watchdog), and pool
# eviction storms (forcing re-encodes) — the gate is LIVENESS: zero hung
# futures, every submission resolves (result or error) inside the timeout
OVERLOAD_FAULT_SPEC = "dispatch:0.15,stall:0.1:0.005,evict:0.1"
OVERLOAD_WATCHDOG_GRACE_S = 2.0
OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_serving.json")


def _run(bundle, params, reqs, *, coalesce: bool, sequential_ref: bool):
    eng = create_engine(
        "flame", bundle, params, n_history=HISTORY, buckets=BUCKETS,
        n_streams=2, feature_mode="sync",
        store=RemoteFeatureStore(latency_s=0.0, feature_dim=12),
        coalesce=coalesce, max_batch=MAX_BATCH, window_s=0.008,
        n_workers=N_WORKERS)
    # warm the feature cache and the executors (steady-state measurement)
    eng.features.query(list(range(N_ITEMS)))
    for r in reqs[:4]:
        eng.serve(r["history"], r["candidates"])
    seq = [eng.serve(r["history"], r["candidates"]) for r in reqs] \
        if sequential_ref else None
    m0 = eng.metrics()
    res = run_workload_async(eng, reqs)
    outputs = res.pop("outputs")
    m1 = eng.metrics()
    chunks = m1["dso_chunks"] - m0["dso_chunks"]
    dispatches = m1["dso_dispatches"] - m0["dso_dispatches"]
    res.update(build_s=eng.dso.build_time_s, chunks=chunks,
               dispatches=dispatches,
               avg_fill=chunks / max(dispatches, 1),
               batch_axis=m1["dso_batch_axis"])
    eng.shutdown()
    return res, outputs, seq


def _repeat_engine(bundle, params, *, history_cache: bool, **engine_kw):
    """Build + warm one repeat-profile engine (hot features, hot pool)."""
    eng = create_engine(
        "flame", bundle, params, n_history=REPEAT_HISTORY, buckets=BUCKETS,
        n_streams=2, feature_mode="sync",
        store=RemoteFeatureStore(latency_s=0.0, feature_dim=12),
        coalesce=True, max_batch=REPEAT_MAX_BATCH, window_s=0.008,
        n_workers=N_WORKERS, history_cache=history_cache,
        pool_slots=POOL_SLOTS, **engine_kw)
    eng.features.query(list(range(N_ITEMS)))
    return eng


def _pool_delta(m0, m1):
    return dict(
        dispatches=m1["dso_dispatches"] - m0["dso_dispatches"],
        encode_dispatches=(m1.get("dso_dispatches_encode", 0)
                           - m0.get("dso_dispatches_encode", 0)),
        pool_hits=m1.get("pool_hits", 0) - m0.get("pool_hits", 0),
        pool_misses=m1.get("pool_misses", 0) - m0.get("pool_misses", 0),
        pool_bytes=m1.get("pool_bytes", 0),
        dedup_rows_saved=(m1.get("dso_dedup_rows_saved", 0)
                          - m0.get("dso_dedup_rows_saved", 0)))


def _ab_interleaved(eng_a, eng_b, reqs, rounds: int = 5):
    """Interleaved A/B throughput measurement.

    CPU CI boxes drift by integer factors across seconds and single passes
    jitter +-25%, so measuring config A start-to-finish and then config B
    bakes both into the ratio.  Alternating measured passes and aggregating
    each side's items/time over all rounds cancels the drift (every A pass
    sits adjacent to a B pass) and averages the jitter — the perf gates
    below are hard asserts, so the ratio must be honest *and* stable.
    Both engines are warmed by one untimed pass first."""
    a, out_a, b, out_b, _ = _ab_interleaved_ratios(eng_a, eng_b, reqs,
                                                   rounds)
    return a, out_a, b, out_b


def _ab_interleaved_ratios(eng_a, eng_b, reqs, rounds: int = 5):
    """Like :func:`_ab_interleaved`, but additionally returns the per-round
    B/A throughput ratios, so gates can use the median ratio (robust to a
    single load-spiked round) instead of the aggregate-time ratio."""
    run_workload_async(eng_a, reqs)
    run_workload_async(eng_b, reqs)
    m0 = [eng_a.metrics(), eng_b.metrics()]
    items_per_pass = sum(len(r["candidates"]) for r in reqs)
    agg = [dict(t=0.0, p50=[], p99=[]), dict(t=0.0, p50=[], p99=[])]
    outs = [None, None]
    ratios = []
    for _ in range(rounds):
        pair_t = [0.0, 0.0]
        for i, eng in enumerate((eng_a, eng_b)):
            r = run_workload_async(eng, reqs)
            outs[i] = r.pop("outputs")
            agg[i]["t"] += r["total_s"]
            pair_t[i] = r["total_s"]
            agg[i]["p50"].append(r["p50_latency_ms"])
            agg[i]["p99"].append(r["p99_latency_ms"])
        ratios.append(pair_t[0] / max(pair_t[1], 1e-9))
    res = []
    for i, eng in enumerate((eng_a, eng_b)):
        res.append({
            "requests": len(reqs) * rounds,
            "throughput_items_per_s": rounds * items_per_pass / agg[i]["t"],
            "p50_latency_ms": float(np.median(agg[i]["p50"])),
            "p99_latency_ms": float(np.median(agg[i]["p99"])),
            **_pool_delta(m0[i], eng.metrics()),
        })
    return res[0], outs[0], res[1], outs[1], ratios


def _run_stale_sweeps_interleaved(bundle, params, n_sweeps: int = 16,
                                  seed: int = 17):
    """Suffix-extension profile: every user's history tail-appends between
    sweeps, so every request arrives as a stale hit.  The re-encode engine
    pays a full window re-encode per request; the incremental engine
    extends the cached prefix (one token per block).  Both engines consume
    identical request streams (same seed) with sweeps interleaved, so the
    outputs are comparable pairwise and machine drift cancels out of the
    throughput ratio."""
    import time as _time
    from repro.serving import ServeRequest

    engines = {}
    for name, inc in (("reencode", False), ("incremental", True)):
        eng = create_engine(
            "flame", bundle, params, n_history=STALE_HISTORY,
            buckets=BUCKETS, n_streams=2, feature_mode="sync",
            store=RemoteFeatureStore(latency_s=0.0, feature_dim=12),
            coalesce=True, max_batch=REPEAT_MAX_BATCH, window_s=0.008,
            n_workers=N_WORKERS, history_cache=True, pool_slots=POOL_SLOTS,
            incremental_history=inc)
        eng.features.query(list(range(N_ITEMS)))
        rng = np.random.default_rng(seed)
        hists = {u: rng.integers(0, N_ITEMS,
                                 STALE_HISTORY + 16).astype(np.int32)
                 for u in range(REPEAT_USERS)}
        engines[name] = dict(eng=eng, rng=rng, hists=hists, outputs=[],
                             lat=[], items=0, time=0.0)

    def one_sweep(state, timed):
        eng, rng, hists = state["eng"], state["rng"], state["hists"]
        if timed:
            for u in range(REPEAT_USERS):         # tail-append => stale
                hists[u] = np.concatenate(
                    [hists[u], rng.integers(0, N_ITEMS, 4).astype(np.int32)])
        t0 = _time.perf_counter()
        futs = []
        for u in range(REPEAT_USERS):
            m = int(rng.choice(REPEAT_COUNTS))
            cand = rng.integers(0, N_ITEMS, m).astype(np.int32)
            futs.append(eng.submit(ServeRequest(history=hists[u],
                                                candidates=cand,
                                                user_id=u)))
        resps = [f.result() for f in futs]
        if timed:
            state["time"] += _time.perf_counter() - t0
            for r in resps:
                state["outputs"].append(r.output)
                state["lat"].append(r.latency_s)
                state["items"] += len(r.output)

    for state in engines.values():                # warm: encode all users
        one_sweep(state, timed=False)
        state["m0"] = state["eng"].metrics()      # counter deltas below
    for _ in range(n_sweeps):
        for state in engines.values():
            one_sweep(state, timed=True)

    results = {}
    for name, state in engines.items():
        m, m0 = state["eng"].metrics(), state["m0"]
        results[name] = ({
            "requests": n_sweeps * REPEAT_USERS,
            "throughput_items_per_s": state["items"] / state["time"],
            "p50_latency_ms": float(np.percentile(state["lat"], 50) * 1e3),
            "p99_latency_ms": float(np.percentile(state["lat"], 99) * 1e3),
            "pool_stale": m["pool_stale"] - m0["pool_stale"],
            "pool_extensions": m["pool_extensions"] - m0["pool_extensions"],
            "encode_dispatches": (m.get("dso_dispatches_encode", 0)
                                  - m0.get("dso_dispatches_encode", 0)),
            "extend_dispatches": (m.get("dso_dispatches_extend", 0)
                                  - m0.get("dso_dispatches_extend", 0)),
        }, state["outputs"])
        state["eng"].shutdown()
    return results["reencode"] + results["incremental"]


def run_fke_profile(bundle, params, csv=True):
    """Profile 6: FKE (impl=fused) vs framework (impl=chunked), both over
    an int8 history pool on the repeat-user workload.  Returns the report
    section and hard-asserts its gates (correctness, >= 1.3x items/s,
    dedup engaged on the fused side)."""
    print("\n=== FKE: fused candidate-scoring engine vs chunked "
          f"(int8 pool, history {FKE_HISTORY}, hot repeat users) ===")
    ftc = TrafficConfig(candidate_counts=REPEAT_COUNTS,
                        distribution="jittered", n_requests=N_REQUESTS,
                        n_history=FKE_HISTORY, seed=29, n_users=REPEAT_USERS)
    freqs = generate_traffic(ftc, n_items=N_ITEMS)

    def fke_engine(impl):
        eng = create_engine(
            "flame", bundle, params, n_history=FKE_HISTORY, buckets=BUCKETS,
            n_streams=2, feature_mode="sync",
            store=RemoteFeatureStore(latency_s=0.0, feature_dim=12),
            coalesce=True, max_batch=REPEAT_MAX_BATCH, window_s=0.008,
            n_workers=FKE_WORKERS, history_cache=True,
            pool_slots=POOL_SLOTS, pool_dtype="int8", impl=impl)
        eng.features.query(list(range(N_ITEMS)))
        return eng

    eng_ch = fke_engine("chunked")
    eng_fu = fke_engine("fused")
    # interleaved per-round ratios, gated on the MEDIAN: a single round
    # poisoned by a CI-box load spike must not decide a hard gate either
    # way (the aggregate-time ratio is still reported)
    chunked, out_ch, fused, out_fu, ratios = _ab_interleaved_ratios(
        eng_ch, eng_fu, freqs, rounds=FKE_ROUNDS)
    eng_ch.shutdown()
    eng_fu.shutdown()
    fke_speedup = float(np.median(ratios))
    fke_speedup_agg = (fused["throughput_items_per_s"]
                       / max(chunked["throughput_items_per_s"], 1e-9))
    fke_max_diff = max(
        float(np.abs(a.astype(np.float32) - b.astype(np.float32)).max())
        for a, b in zip(out_ch, out_fu))
    print(f"{'config':<28}{'items/s':>10}{'p50 ms':>9}{'p99 ms':>9}"
          f"{'dedup':>7}")
    for name, r in (("chunked (framework ops)", chunked),
                    ("fused (FKE kernels)", fused)):
        print(f"{name:<28}{r['throughput_items_per_s']:>10.0f}"
              f"{r['p50_latency_ms']:>9.1f}{r['p99_latency_ms']:>9.1f}"
              f"{r['dedup_rows_saved']:>7}")
    print(f"-> FKE: throughput x{fke_speedup:.2f} median per-round "
          f"(x{fke_speedup_agg:.2f} aggregate) vs chunked (fused reads "
          f"int8 rows + dedup index in-kernel: no host dequant, no kv[idx] "
          f"copy); max |diff| {fke_max_diff:.2e}; dedup auto-on saved "
          f"{fused['dedup_rows_saved']} row restacks on this backend")
    if csv:
        print(f"serving/fke_chunked,{chunked['p50_latency_ms'] * 1e3:.1f},"
              f"tput={chunked['throughput_items_per_s']:.0f}")
        print(f"serving/fke_fused,{fused['p50_latency_ms'] * 1e3:.1f},"
              f"tput={fused['throughput_items_per_s']:.0f}")

    if fke_max_diff > FKE_TOL:
        raise AssertionError(
            f"fused scores diverged from chunked by {fke_max_diff:.2e} "
            f"(> {FKE_TOL}) on the shared int8 pool — correctness gate "
            f"failed")
    if fke_speedup < FKE_SPEEDUP_MIN:
        raise AssertionError(
            f"FKE median per-round speedup x{fke_speedup:.2f} < "
            f"{FKE_SPEEDUP_MIN} vs impl=chunked on the repeat-user profile "
            f"(per-round ratios {[round(r, 2) for r in ratios]}) — perf "
            f"gate failed")
    if fused["dedup_rows_saved"] < 1:
        raise AssertionError(
            "fused engine saved no KV-row restacks — in-kernel dedup is "
            "not engaging (it must auto-enable on every backend)")
    return {
        "workload": {"distribution": "jittered",
                     "counts": list(REPEAT_COUNTS),
                     "n_requests": N_REQUESTS, "history": FKE_HISTORY,
                     "n_users": REPEAT_USERS, "pool_dtype": "int8",
                     "max_batch": REPEAT_MAX_BATCH},
        "chunked": chunked,
        "fused": fused,
        "speedup_items_per_s": fke_speedup_agg,
        "speedup_median_per_round": fke_speedup,
        "per_round_ratios": [float(r) for r in ratios],
        "max_abs_diff_vs_chunked": fke_max_diff,
        "gates": {"fke_speedup_min": FKE_SPEEDUP_MIN,
                  "fke_tolerance": FKE_TOL,
                  "fke_dedup_nonzero": True},
    }


def _cached_padded_fraction(m0: dict, m1: dict) -> float:
    """Padded fraction of the cached-scoring dispatches between two metric
    snapshots: 1 - real candidates / dispatched candidate slots."""
    slots = m1.get("dso_cand_slots_cached", 0) - m0.get(
        "dso_cand_slots_cached", 0)
    valid = m1.get("dso_cand_valid_cached", 0) - m0.get(
        "dso_cand_valid_cached", 0)
    return 1.0 - valid / slots if slots else 0.0


def run_dso_nonuniform_profile(bundle, params, csv=True):
    """Profile 7: DSO v2 segment packing + deadline-aware flushing vs PR-4
    coalescing on non-uniform (zipf + lognormal) candidate traffic over a
    hot history pool.  Gates (zipf side): packed >= 1.2x items/s median
    per-round, padded_fraction reduced >= 2x, scores within the cross-
    executable tolerance."""
    print("\n=== DSO v2: segment-packed ragged dispatch vs PR-4 coalescing "
          f"(history {DSO_HISTORY}, counts {DSO_COUNTS}, bucket "
          f"{DSO_BUCKETS}, hot pool) ===")

    def dso_engine(pack):
        eng = create_engine(
            "flame", bundle, params, n_history=DSO_HISTORY,
            buckets=DSO_BUCKETS, n_streams=DSO_STREAMS, feature_mode="sync",
            store=RemoteFeatureStore(latency_s=0.0, feature_dim=12),
            coalesce=True, max_batch=REPEAT_MAX_BATCH, window_s=0.008,
            n_workers=N_WORKERS, history_cache=True,
            pool_slots=POOL_SLOTS, impl="fused", pack_tails=pack)
        eng.features.query(list(range(N_ITEMS)))
        return eng

    report = {"workload": {"counts": list(DSO_COUNTS),
                           "n_requests": N_REQUESTS, "history": DSO_HISTORY,
                           "n_users": REPEAT_USERS, "impl": "fused",
                           "max_batch": REPEAT_MAX_BATCH,
                           "buckets": list(DSO_BUCKETS)},
              "gates": {"dso_pack_speedup_min": DSO_SPEEDUP_MIN,
                        "dso_pad_ratio_min": DSO_PAD_RATIO_MIN,
                        "dso_tolerance": DSO_TOL}}
    for dist in ("zipf", "lognormal"):
        tc = TrafficConfig(candidate_counts=DSO_COUNTS, distribution=dist,
                           n_requests=N_REQUESTS, n_history=DSO_HISTORY,
                           seed=31, n_users=REPEAT_USERS)
        reqs = generate_traffic(tc, n_items=N_ITEMS)
        eng_un, eng_pk = dso_engine(False), dso_engine(True)
        m0 = [eng_un.metrics(), eng_pk.metrics()]
        unpacked, out_un, packed, out_pk, ratios = _ab_interleaved_ratios(
            eng_un, eng_pk, reqs, rounds=DSO_ROUNDS)
        pf_un = _cached_padded_fraction(m0[0], eng_un.metrics())
        pf_pk = _cached_padded_fraction(m0[1], eng_pk.metrics())
        eng_un.shutdown()
        eng_pk.shutdown()
        speedup = float(np.median(ratios))
        speedup_agg = (packed["throughput_items_per_s"]
                       / max(unpacked["throughput_items_per_s"], 1e-9))
        max_diff = max(
            float(np.abs(a.astype(np.float32) - b.astype(np.float32)).max())
            for a, b in zip(out_un, out_pk))
        bitwise_frac = float(np.mean([np.array_equal(a, b)
                                      for a, b in zip(out_un, out_pk)]))
        print(f"-- {dist} traffic --")
        print(f"{'config':<28}{'items/s':>10}{'p50 ms':>9}{'p99 ms':>9}"
              f"{'padded':>8}")
        for name, r, pf in (("unpacked (PR-4 coalescing)", unpacked, pf_un),
                            ("packed (DSO v2)", packed, pf_pk)):
            print(f"{name:<28}{r['throughput_items_per_s']:>10.0f}"
                  f"{r['p50_latency_ms']:>9.1f}{r['p99_latency_ms']:>9.1f}"
                  f"{pf:>8.2f}")
        print(f"-> packing ({dist}): throughput x{speedup:.2f} median "
              f"per-round (x{speedup_agg:.2f} aggregate); padded_fraction "
              f"{pf_un:.2f} -> {pf_pk:.2f} "
              f"({pf_un / max(pf_pk, 1e-9):.1f}x less padding); max |diff| "
              f"{max_diff:.2e}, bitwise on {bitwise_frac:.0%} of requests")
        if csv:
            print(f"serving/dso_{dist}_unpacked,"
                  f"{unpacked['p50_latency_ms'] * 1e3:.1f},"
                  f"tput={unpacked['throughput_items_per_s']:.0f}")
            print(f"serving/dso_{dist}_packed,"
                  f"{packed['p50_latency_ms'] * 1e3:.1f},"
                  f"tput={packed['throughput_items_per_s']:.0f}")
        report[dist] = {
            "unpacked": dict(unpacked, padded_fraction=pf_un),
            "packed": dict(packed, padded_fraction=pf_pk),
            "speedup_items_per_s": speedup_agg,
            "speedup_median_per_round": speedup,
            "per_round_ratios": [float(r) for r in ratios],
            "padded_fraction_ratio": pf_un / max(pf_pk, 1e-9),
            "max_abs_diff_vs_unpacked": max_diff,
            "bitwise_fraction": bitwise_frac,
        }
        if max_diff > DSO_TOL:
            raise AssertionError(
                f"packed scores diverged from unpacked by {max_diff:.2e} "
                f"(> {DSO_TOL}) on {dist} traffic — correctness gate failed")
        if dist == "zipf":
            if speedup < DSO_SPEEDUP_MIN:
                raise AssertionError(
                    f"DSO v2 packing x{speedup:.2f} < {DSO_SPEEDUP_MIN} "
                    f"median per-round vs PR-4 coalescing on zipf traffic "
                    f"(per-round {[round(r, 2) for r in ratios]}) — perf "
                    f"gate failed")
            if pf_un < DSO_PAD_RATIO_MIN * pf_pk:
                raise AssertionError(
                    f"padded_fraction only {pf_un:.2f} -> {pf_pk:.2f} on "
                    f"zipf traffic (< {DSO_PAD_RATIO_MIN}x reduction) — "
                    f"packing is not reclaiming the tail padding")
    return report


#: Runs inside a forced-4-device subprocess (see run_sharded_profile):
#: XLA_FLAGS must be set before jax imports anywhere in the process, so the
#: whole A/B — engine builds, traffic, interleaved rounds — happens here and
#: ships one JSON line back on stdout.
_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = \
    "--xla_force_host_platform_device_count={devices}"
import json
import sys

sys.path.insert(0, "src")
import numpy as np

from benchmarks.bench_serving import (BUCKETS, N_ITEMS, N_REQUESTS,
                                      N_WORKERS, POOL_SLOTS, REPEAT_COUNTS,
                                      REPEAT_HISTORY, REPEAT_MAX_BATCH,
                                      REPEAT_USERS, _ab_interleaved_ratios)
from benchmarks.common import make_climber
from repro.core.pda import RemoteFeatureStore
from repro.launch.mesh import make_host_mesh
from repro.serving import create_engine
from repro.serving.scheduler import TrafficConfig, generate_traffic

cfg, bundle, params = make_climber(d_model=64, layers=2, blocks=2)
tc = TrafficConfig(candidate_counts=REPEAT_COUNTS, distribution="jittered",
                   n_requests=N_REQUESTS, n_history=REPEAT_HISTORY,
                   seed=13, n_users=REPEAT_USERS)
reqs = generate_traffic(tc, n_items=N_ITEMS)


def engine(mesh):
    eng = create_engine(
        "flame", bundle, params, n_history=REPEAT_HISTORY, buckets=BUCKETS,
        n_streams=2, feature_mode="sync",
        store=RemoteFeatureStore(latency_s=0.0, feature_dim=12),
        coalesce=True, max_batch=REPEAT_MAX_BATCH, window_s=0.008,
        n_workers=N_WORKERS, history_cache=True, pool_slots=POOL_SLOTS,
        mesh=mesh)
    eng.features.query(list(range(N_ITEMS)))
    return eng


eng_single = engine(None)
eng_sharded = engine(make_host_mesh(model_parallel={model_parallel}))
single, out_s, sharded, out_m, ratios = _ab_interleaved_ratios(
    eng_single, eng_sharded, reqs, rounds={rounds})
metrics = eng_sharded.metrics()
shard_bytes = sorted(int(metrics[k]) for k in metrics
                     if k.startswith("pool_bytes_used_shard"))
max_diff = max(
    float(np.abs(a.astype(np.float32) - b.astype(np.float32)).max())
    for a, b in zip(out_s, out_m))
bitwise_frac = float(np.mean([np.array_equal(a, b)
                              for a, b in zip(out_s, out_m)]))
eng_single.shutdown()
eng_sharded.shutdown()
print("RESULT " + json.dumps({{
    "single": single, "sharded": sharded,
    "per_round_ratios": [float(r) for r in ratios],
    "max_abs_diff_vs_single": max_diff,
    "bitwise_fraction": bitwise_frac,
    "pool_bytes_used_per_shard": shard_bytes,
    "pool_bytes_used_total": int(metrics.get("pool_bytes_used", 0)),
    "pool_shard_ways": int(metrics.get("pool_shard_ways", 0)),
    "dso_batch_axis": int(metrics.get("dso_batch_axis", 0)),
}}))
"""


def run_sharded_profile(bundle, params, csv=True):
    """Profile 8 (sharded): mesh-sharded serving vs single-device on the
    repeat-user workload, A/B-interleaved inside a forced-4-device
    subprocess.  ``bundle``/``params`` are unused — the subprocess rebuilds
    the same seeded model because the device count is fixed at jax import.
    Gates: median per-round throughput ratio >= the CPU parity floor, score
    agreement within the TP reassociation tolerance, and the pool byte
    budget actually split across model shards."""
    import subprocess
    import sys

    del bundle, params
    if jax.default_backend() != "cpu":
        # the forced-host-device child needs a backend of its own, but this
        # process has touched JAX and holds the accelerator; the mesh path
        # on real chips is `python chip_smoke.py --four-chips`
        raise SystemExit(
            "bench_serving --profile sharded emulates a 4-device mesh on "
            "the CPU backend only (run with JAX_PLATFORMS=cpu); on TPU "
            "chips run `python chip_smoke.py --four-chips`")
    print("\n=== Sharded serving: (data=2, model=2) host mesh vs "
          f"single-device (forced {SHARDED_DEVICES} devices, repeat-user "
          f"workload, history {REPEAT_HISTORY}) ===")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)      # the script pins its own device count
    proc = subprocess.run(
        [sys.executable, "-c",
         _SHARDED_SCRIPT.format(devices=SHARDED_DEVICES,
                                model_parallel=SHARDED_MODEL_PARALLEL,
                                rounds=SHARDED_ROUNDS)],
        capture_output=True, text=True, env=env,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    if proc.returncode != 0:
        raise AssertionError(
            f"sharded A/B subprocess failed "
            f"(rc={proc.returncode}):\n{proc.stdout}\n{proc.stderr}")
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    res = json.loads(line[len("RESULT "):])
    single, sharded = res["single"], res["sharded"]
    speedup = float(np.median(res["per_round_ratios"]))
    speedup_agg = (sharded["throughput_items_per_s"]
                   / max(single["throughput_items_per_s"], 1e-9))
    print(f"{'config':<28}{'items/s':>10}{'p50 ms':>9}{'p99 ms':>9}")
    for name, r in (("single-device", single),
                    (f"sharded (2,2) x{SHARDED_DEVICES}dev", sharded)):
        print(f"{name:<28}{r['throughput_items_per_s']:>10.0f}"
              f"{r['p50_latency_ms']:>9.1f}{r['p99_latency_ms']:>9.1f}")
    print(f"-> sharded: throughput x{speedup:.2f} median per-round "
          f"(x{speedup_agg:.2f} aggregate) vs single-device on one CPU "
          f"({SHARDED_PARITY_MIN} parity floor — devices are emulated); "
          f"max |diff| {res['max_abs_diff_vs_single']:.2e}, bitwise on "
          f"{res['bitwise_fraction']:.0%}; pool bytes/shard "
          f"{res['pool_bytes_used_per_shard']} "
          f"({res['pool_shard_ways']} shard ways)")
    if csv:
        print(f"serving/sharded_single,{single['p50_latency_ms'] * 1e3:.1f},"
              f"tput={single['throughput_items_per_s']:.0f}")
        print(f"serving/sharded_mesh,{sharded['p50_latency_ms'] * 1e3:.1f},"
              f"tput={sharded['throughput_items_per_s']:.0f}")

    if res["max_abs_diff_vs_single"] > SHARDED_TOL:
        raise AssertionError(
            f"sharded scores diverged from single-device by "
            f"{res['max_abs_diff_vs_single']:.2e} (> {SHARDED_TOL}) — "
            f"correctness gate failed")
    if speedup < SHARDED_PARITY_MIN:
        raise AssertionError(
            f"sharded serving x{speedup:.2f} < {SHARDED_PARITY_MIN} median "
            f"per-round vs single-device (per-round ratios "
            f"{[round(r, 2) for r in res['per_round_ratios']]}) — the mesh "
            f"machinery is taxing the hot path beyond CPU-emulation "
            f"overhead")
    shard_bytes = res["pool_bytes_used_per_shard"]
    if res["pool_shard_ways"] != SHARDED_MODEL_PARALLEL or \
            len(set(shard_bytes)) != 1 or shard_bytes[0] <= 0 or \
            shard_bytes[0] * SHARDED_MODEL_PARALLEL != \
            res["pool_bytes_used_total"]:
        raise AssertionError(
            f"per-shard pool budget not split {SHARDED_MODEL_PARALLEL} "
            f"ways: shards {shard_bytes}, ways {res['pool_shard_ways']}, "
            f"total {res['pool_bytes_used_total']}")
    return {
        "workload": {"distribution": "jittered",
                     "counts": list(REPEAT_COUNTS),
                     "n_requests": N_REQUESTS, "history": REPEAT_HISTORY,
                     "n_users": REPEAT_USERS,
                     "max_batch": REPEAT_MAX_BATCH,
                     "devices": SHARDED_DEVICES,
                     "mesh": [SHARDED_DEVICES // SHARDED_MODEL_PARALLEL,
                              SHARDED_MODEL_PARALLEL]},
        "single_device": single,
        "sharded": sharded,
        "speedup_items_per_s": speedup_agg,
        "speedup_median_per_round": speedup,
        "per_round_ratios": res["per_round_ratios"],
        "max_abs_diff_vs_single": res["max_abs_diff_vs_single"],
        "bitwise_fraction": res["bitwise_fraction"],
        "pool_bytes_used_per_shard": shard_bytes,
        "pool_bytes_used_total": res["pool_bytes_used_total"],
        "pool_shard_ways": res["pool_shard_ways"],
        "global_batch_axis": res["dso_batch_axis"],
        "gates": {"sharded_parity_min": SHARDED_PARITY_MIN,
                  "sharded_tolerance": SHARDED_TOL,
                  "sharded_pool_split": True},
    }


def _decode_traffic(seed):
    """Zipf repeat-user decode traffic, alternating top-k and beam
    requests so one executor set serves both ranking policies."""
    from repro.serving.api import BeamConfig, TopKConfig

    tc = TrafficConfig(candidate_counts=DECODE_COUNTS, distribution="zipf",
                       n_requests=DECODE_REQUESTS, n_history=DECODE_HISTORY,
                       seed=seed, n_users=REPEAT_USERS)
    reqs = generate_traffic(tc, n_items=N_ITEMS)
    for i, r in enumerate(reqs):
        r["generate"] = (TopKConfig(k=DECODE_BEAM, steps=DECODE_STEPS)
                         if i % 2 == 0 else
                         BeamConfig(width=DECODE_BEAM, steps=DECODE_STEPS))
    return reqs


def _decode_engine(bundle, params, *, pack, impl="chunked", pool_dtype=None,
                   faults=None):
    kw = {"pool_dtype": pool_dtype} if pool_dtype else {}
    eng = create_engine(
        "flame", bundle, params, n_history=DECODE_HISTORY,
        buckets=BUCKETS, n_streams=2, feature_mode="sync",
        store=RemoteFeatureStore(latency_s=0.0, feature_dim=12),
        coalesce=True, max_batch=REPEAT_MAX_BATCH, window_s=0.008,
        n_workers=DECODE_WORKERS, history_cache=True,
        pool_slots=POOL_SLOTS, generate=DECODE_STEPS, pack_tails=pack,
        impl=impl, faults=faults, **kw)
    eng.features.query(list(range(N_ITEMS)))
    return eng


def _decode_chaos_pass(bundle, params, reqs, *, impl):
    """Chaos arm shared by the decode profiles: DECODE_FAULT_SPEC injects
    transient dispatch faults into decode-step dispatches and pool
    eviction storms that evict parked beam caches mid-generation.  Gates:
    zero hung futures (liveness) and ``gen_replays`` > 0 (an evicted beam
    re-decodes from the root instead of failing the request)."""
    from repro.serving.faults import FaultInjector

    faults = FaultInjector.parse(DECODE_FAULT_SPEC, seed=43)
    eng = _decode_engine(bundle, params, pack=True, impl=impl,
                         pool_dtype="int8", faults=faults)
    hung = 0
    last = {}
    for _ in range(2):
        r = run_workload_async(eng, reqs, tolerate_errors=True)
        hung += r["hung"]
        last = {k: r[k] for k in ("resolved", "rejected", "failed")}
    m = eng.metrics()
    eng.shutdown()
    chaos = dict(
        last, hung_total=hung, impl=impl, fault_spec=DECODE_FAULT_SPEC,
        fault_dispatch_fired=int(m.get("fault_dispatch_fired", 0)),
        fault_evict_fired=int(m.get("fault_evict_fired", 0)),
        dispatch_retries=int(m.get("dso_dispatch_retries", 0)),
        gen_replays=int(m.get("gen_replays", 0)))
    print(f"-> decode chaos ({impl}, {DECODE_FAULT_SPEC}): "
          f"{chaos['fault_dispatch_fired']} dispatch faults "
          f"({chaos['dispatch_retries']} retried), "
          f"{chaos['fault_evict_fired']} eviction storms, "
          f"{chaos['gen_replays']} beam replays; hung futures: {hung}")
    if hung:
        raise AssertionError(
            f"{hung} decode future(s) never resolved under fault "
            f"injection — the zero-hung liveness gate failed")
    if chaos["fault_dispatch_fired"] < 1 or chaos["fault_evict_fired"] < 1:
        raise AssertionError(
            "decode chaos pass fired no dispatch/evict faults — the "
            "injector is not engaging (seed/spec drift?)")
    if chaos["gen_replays"] < 1:
        raise AssertionError(
            "eviction storms never forced a mid-generation beam replay — "
            "the parked-beam recovery path is not being exercised")
    return chaos


def run_decode_profile(bundle, params, csv=True):
    """Profile 9: generative decode — DSO-packed beam decode vs per-request
    dispatch on zipf repeat-user traffic with alternating top-k and beam
    requests.  Each decode step on the unpacked side is one (width, bucket)
    dispatch per request; the packed side fills shared rows with beam
    segments from many in-flight requests.  Gates: exact token-sequence
    equality (both sides run the same row-wise batch-invariant AOT
    executables, so sequences must match bitwise), median per-round
    gen-tokens/s ratio >= DECODE_SPEEDUP_MIN (cpu-count-aware, see the
    constant), the packer actually engaging (packed segments > 0), and
    the shared chaos arm (zero hung futures, beam replays firing)."""
    print("\n=== Generative decode: DSO-packed beam rows vs per-request "
          f"dispatch (history {DECODE_HISTORY}, universes {DECODE_COUNTS} "
          f"zipf, {DECODE_STEPS} steps, width {DECODE_BEAM}) ===")
    reqs = _decode_traffic(seed=23)
    eng_packed = _decode_engine(bundle, params, pack=True)
    eng_plain = _decode_engine(bundle, params, pack=False)
    # warm both sides (compiles the decode/append executors and encodes
    # every user's history into the pool), then interleave measured rounds
    # — same drift-cancelling protocol as _ab_interleaved_ratios, but the
    # item unit here is GENERATED TOKENS, which that helper (built for
    # scoring traffic) would miscount from len(candidates)
    run_workload_async(eng_packed, reqs)
    run_workload_async(eng_plain, reqs)
    m0 = [eng_packed.metrics(), eng_plain.metrics()]
    agg = [dict(t=0.0, p50=[], p99=[]), dict(t=0.0, p50=[], p99=[])]
    outs = [None, None]
    ratios = []
    for _ in range(DECODE_ROUNDS):
        pair_t = [0.0, 0.0]
        for i, eng in enumerate((eng_packed, eng_plain)):
            r = run_workload_async(eng, reqs)
            outs[i] = r.pop("outputs")
            agg[i]["t"] += r["total_s"]
            pair_t[i] = r["total_s"]
            agg[i]["p50"].append(r["p50_latency_ms"])
            agg[i]["p99"].append(r["p99_latency_ms"])
        ratios.append(pair_t[1] / max(pair_t[0], 1e-9))  # plain_t/packed_t
    res = []
    for i, eng in enumerate((eng_packed, eng_plain)):
        tokens_per_pass = sum(int((o >= 0).sum()) for o in outs[i])
        m1 = eng.metrics()
        res.append({
            "requests": len(reqs) * DECODE_ROUNDS,
            "gen_tokens_per_s": (DECODE_ROUNDS * tokens_per_pass
                                 / max(agg[i]["t"], 1e-9)),
            "p50_latency_ms": float(np.median(agg[i]["p50"])),
            "p99_latency_ms": float(np.median(agg[i]["p99"])),
            "decode_dispatches": (m1.get("dso_dispatches_decode", 0)
                                  - m0[i].get("dso_dispatches_decode", 0)),
            "append_dispatches": (m1.get("dso_dispatches_append", 0)
                                  - m0[i].get("dso_dispatches_append", 0)),
            "packed_segments": (m1.get("dso_packed_segments", 0)
                                - m0[i].get("dso_packed_segments", 0)),
            **_pool_delta(m0[i], m1),
        })
        eng.shutdown()
    packed, plain = res
    seq_bitwise = all(np.array_equal(a, b)
                      for a, b in zip(outs[0], outs[1]))
    speedup = float(np.median(ratios))
    speedup_agg = (packed["gen_tokens_per_s"]
                   / max(plain["gen_tokens_per_s"], 1e-9))
    print(f"{'config':<26}{'gen tok/s':>10}{'p50 ms':>9}{'p99 ms':>9}"
          f"{'decode':>8}{'packed':>8}")
    for name, r in (("per-request decode", plain),
                    ("packed beam rows", packed)):
        print(f"{name:<26}{r['gen_tokens_per_s']:>10.0f}"
              f"{r['p50_latency_ms']:>9.1f}{r['p99_latency_ms']:>9.1f}"
              f"{r['decode_dispatches']:>8}{r['packed_segments']:>8}")
    print(f"-> decode packing: x{speedup:.2f} median per-round "
          f"(x{speedup_agg:.2f} aggregate); sequences bitwise-identical "
          f"across engines: {seq_bitwise}")
    if csv:
        print(f"serving/decode_unpacked,{plain['p50_latency_ms'] * 1e3:.1f},"
              f"tput={plain['gen_tokens_per_s']:.0f}")
        print(f"serving/decode_packed,{packed['p50_latency_ms'] * 1e3:.1f},"
              f"tput={packed['gen_tokens_per_s']:.0f}")

    if not seq_bitwise:
        raise AssertionError(
            "packed decode generated different token sequences than the "
            "per-request engine — correctness gate failed (same AOT "
            "executables must be row-wise batch-invariant)")
    if speedup < DECODE_SPEEDUP_MIN:
        raise AssertionError(
            f"packed decode median per-round speedup x{speedup:.2f} < "
            f"{DECODE_SPEEDUP_MIN} (per-round ratios "
            f"{[round(r, 2) for r in ratios]}) — perf gate failed")
    if packed["packed_segments"] < 1:
        raise AssertionError(
            "packed engine reported no packed segments during decode — "
            "the beam packer is not engaging on this traffic")
    chaos = _decode_chaos_pass(bundle, params, reqs, impl="chunked")
    return {
        "workload": {"distribution": "zipf", "counts": list(DECODE_COUNTS),
                     "n_requests": DECODE_REQUESTS,
                     "history": DECODE_HISTORY, "n_users": REPEAT_USERS,
                     "steps": DECODE_STEPS, "width": DECODE_BEAM,
                     "max_batch": REPEAT_MAX_BATCH},
        "unpacked": plain,
        "packed": packed,
        "speedup_gen_tokens_per_s": speedup_agg,
        "speedup_median_per_round": speedup,
        "per_round_ratios": [float(r) for r in ratios],
        "sequences_bitwise": bool(seq_bitwise),
        "chaos": chaos,
        "gates": {"decode_speedup_min": DECODE_SPEEDUP_MIN,
                  "decode_sequences_bitwise": True,
                  "decode_packed_segments_nonzero": True,
                  "decode_chaos_zero_hung": True,
                  "decode_chaos_gen_replays_nonzero": True},
    }


def run_decode_fused_profile(bundle, params, csv=True):
    """Profile 11 (FKE v2): fused generative decode — the lengths-masked
    fused kernel scores each decode step in ONE executor call against
    stored pool KV — vs the chunked per-pass decode, both segment-packed.
    Two passes: a NATIVE-pool parity pass (exact f32 math on both sides;
    every generated sequence must match token for token) and an int8-pool
    timed A/B (interleaved rounds, median per-round gen-tokens/s ratio
    >= DECODE_FUSED_SPEEDUP_MIN, cpu-count-aware).  The fused side must
    report zero ``packed_kernel_reroutes`` (the bq-alignment contract
    holds end to end) and the shared chaos arm runs against the fused
    engine (zero hung futures, beam replays firing)."""
    print("\n=== Fused generative decode (FKE v2): fused vs chunked "
          f"decode formulation (history {DECODE_HISTORY}, universes "
          f"{DECODE_COUNTS} zipf, {DECODE_STEPS} steps, width "
          f"{DECODE_BEAM}) ===")
    reqs = _decode_traffic(seed=31)

    # ---- native-pool parity pass: token-for-token sequence gate ----
    eng_ch = _decode_engine(bundle, params, pack=False, impl="chunked")
    eng_fu = _decode_engine(bundle, params, pack=False, impl="fused")
    want = run_workload_async(eng_ch, reqs)["outputs"]
    got = run_workload_async(eng_fu, reqs)["outputs"]
    seq_ok = all(np.array_equal(a, b) for a, b in zip(want, got))
    eng_ch.shutdown()
    eng_fu.shutdown()
    print(f"-> native-pool parity: fused sequences token-for-token equal "
          f"to chunked: {seq_ok} ({len(want)} requests)")

    # ---- int8-pool timed pass: interleaved A/B rounds ----
    eng_fused = _decode_engine(bundle, params, pack=True, impl="fused",
                               pool_dtype="int8")
    eng_chunk = _decode_engine(bundle, params, pack=True, impl="chunked",
                               pool_dtype="int8")
    run_workload_async(eng_fused, reqs)        # warm: compile + encode pool
    run_workload_async(eng_chunk, reqs)
    m0 = [eng_fused.metrics(), eng_chunk.metrics()]
    agg = [dict(t=0.0, p50=[], p99=[]), dict(t=0.0, p50=[], p99=[])]
    outs = [None, None]
    ratios = []
    for _ in range(DECODE_ROUNDS):
        pair_t = [0.0, 0.0]
        for i, eng in enumerate((eng_fused, eng_chunk)):
            r = run_workload_async(eng, reqs)
            outs[i] = r.pop("outputs")
            agg[i]["t"] += r["total_s"]
            pair_t[i] = r["total_s"]
            agg[i]["p50"].append(r["p50_latency_ms"])
            agg[i]["p99"].append(r["p99_latency_ms"])
        ratios.append(pair_t[1] / max(pair_t[0], 1e-9))  # chunked_t/fused_t
    res = []
    for i, eng in enumerate((eng_fused, eng_chunk)):
        tokens_per_pass = sum(int((o >= 0).sum()) for o in outs[i])
        m1 = eng.metrics()
        res.append({
            "requests": len(reqs) * DECODE_ROUNDS,
            "gen_tokens_per_s": (DECODE_ROUNDS * tokens_per_pass
                                 / max(agg[i]["t"], 1e-9)),
            "p50_latency_ms": float(np.median(agg[i]["p50"])),
            "p99_latency_ms": float(np.median(agg[i]["p99"])),
            "decode_dispatches": (m1.get("dso_dispatches_decode", 0)
                                  - m0[i].get("dso_dispatches_decode", 0)),
            "packed_segments": (m1.get("dso_packed_segments", 0)
                                - m0[i].get("dso_packed_segments", 0)),
            "packed_kernel_reroutes": int(
                m1.get("packed_kernel_reroutes", 0)),
            **_pool_delta(m0[i], m1),
        })
        eng.shutdown()
    fused, chunked = res
    # int8 pools: the two formulations round differently, so sequences may
    # legitimately diverge where quantized logits tie — report, don't gate
    int8_match = float(np.mean([np.array_equal(a, b)
                                for a, b in zip(outs[0], outs[1])]))
    speedup = float(np.median(ratios))
    speedup_agg = (fused["gen_tokens_per_s"]
                   / max(chunked["gen_tokens_per_s"], 1e-9))
    print(f"{'config':<26}{'gen tok/s':>10}{'p50 ms':>9}{'p99 ms':>9}"
          f"{'decode':>8}{'packed':>8}")
    for name, r in (("chunked decode (int8)", chunked),
                    ("fused decode (int8)", fused)):
        print(f"{name:<26}{r['gen_tokens_per_s']:>10.0f}"
              f"{r['p50_latency_ms']:>9.1f}{r['p99_latency_ms']:>9.1f}"
              f"{r['decode_dispatches']:>8}{r['packed_segments']:>8}")
    print(f"-> fused decode: x{speedup:.2f} median per-round "
          f"(x{speedup_agg:.2f} aggregate) vs chunked; int8 sequence "
          f"agreement {int8_match:.2f}; packed kernel reroutes "
          f"{fused['packed_kernel_reroutes']}")
    if csv:
        print(f"serving/decode_chunked_int8,"
              f"{chunked['p50_latency_ms'] * 1e3:.1f},"
              f"tput={chunked['gen_tokens_per_s']:.0f}")
        print(f"serving/decode_fused_int8,"
              f"{fused['p50_latency_ms'] * 1e3:.1f},"
              f"tput={fused['gen_tokens_per_s']:.0f}")

    if not seq_ok:
        raise AssertionError(
            "fused decode generated different token sequences than the "
            "chunked engine on the NATIVE pool — correctness gate failed "
            "(both sides run exact f32 math over the same stored values)")
    if fused["packed_kernel_reroutes"]:
        raise AssertionError(
            f"{fused['packed_kernel_reroutes']} packed kernel dispatch(es) "
            f"rerouted to the jnp formulation — the bq-alignment contract "
            f"is not holding on the fused engine")
    if speedup < DECODE_FUSED_SPEEDUP_MIN:
        raise AssertionError(
            f"fused decode median per-round speedup x{speedup:.2f} < "
            f"{DECODE_FUSED_SPEEDUP_MIN} vs chunked (per-round ratios "
            f"{[round(r, 2) for r in ratios]}) — perf gate failed")
    chaos = _decode_chaos_pass(bundle, params, reqs, impl="fused")
    return {
        "workload": {"distribution": "zipf", "counts": list(DECODE_COUNTS),
                     "n_requests": DECODE_REQUESTS,
                     "history": DECODE_HISTORY, "n_users": REPEAT_USERS,
                     "steps": DECODE_STEPS, "width": DECODE_BEAM,
                     "max_batch": REPEAT_MAX_BATCH,
                     "pool_dtype_timed": "int8",
                     "cpu_count": int(os.cpu_count() or 1)},
        "chunked": chunked,
        "fused": fused,
        "speedup_gen_tokens_per_s": speedup_agg,
        "speedup_median_per_round": speedup,
        "per_round_ratios": [float(r) for r in ratios],
        "native_sequences_token_for_token": bool(seq_ok),
        "int8_sequence_agreement": int8_match,
        "chaos": chaos,
        "gates": {"decode_fused_speedup_min": DECODE_FUSED_SPEEDUP_MIN,
                  "decode_fused_native_sequences": True,
                  "decode_fused_zero_reroutes": True,
                  "decode_fused_chaos_zero_hung": True,
                  "decode_fused_chaos_gen_replays_nonzero": True},
    }


def run_overload_profile(bundle, params, csv=True):
    """Profile 10 (overload): SLO-tiered EDF admission + shedding vs FIFO
    under sustained overload, plus a chaos pass under fault injection.
    Gates: EDF interactive-tier goodput-under-SLO >= OVERLOAD_GOODPUT_MIN x
    FIFO (median per-round, +1-smoothed), zero hung futures everywhere,
    and the chaos arms actually firing."""
    from repro.serving.api import DegradationPolicy
    from repro.serving.faults import FaultInjector

    print("\n=== Overload: EDF admission + tiered shedding vs FIFO "
          f"(lognormal traffic, {OVERLOAD_REQUESTS} reqs -> "
          f"{OVERLOAD_WORKERS} workers, queue {OVERLOAD_PENDING}, "
          f"SLOs {OVERLOAD_TIER_SLO}) ===")
    tc = TrafficConfig(candidate_counts=OVERLOAD_COUNTS,
                       distribution="lognormal",
                       n_requests=OVERLOAD_REQUESTS,
                       n_history=OVERLOAD_HISTORY, seed=37,
                       n_users=REPEAT_USERS, tier_mix=OVERLOAD_TIER_MIX)
    reqs = generate_traffic(tc, n_items=N_ITEMS)

    def overload_engine(admission, shed, faults=None, degradation=None,
                        watchdog=0.0):
        eng = create_engine(
            "flame", bundle, params, n_history=OVERLOAD_HISTORY,
            buckets=BUCKETS, n_streams=2, feature_mode="sync",
            store=RemoteFeatureStore(latency_s=0.0, feature_dim=12),
            coalesce=True, max_batch=MAX_BATCH, window_s=0.002,
            n_workers=OVERLOAD_WORKERS, max_pending=OVERLOAD_PENDING,
            history_cache=True, pool_slots=POOL_SLOTS,
            admission=admission, shed_policy=shed,
            slo_tier_defaults=dict(OVERLOAD_TIER_SLO),
            faults=faults, degradation=degradation,
            watchdog_grace_s=watchdog)
        eng.features.query(list(range(N_ITEMS)))
        return eng

    eng_fifo = overload_engine("fifo", "none")
    eng_edf = overload_engine("edf", "tiered")
    # warm both sides (executors compiled, pool encoded), then interleave
    # measured rounds; goodput is a COUNTER, so each round reads the delta
    run_workload_async(eng_fifo, reqs, tolerate_errors=True)
    run_workload_async(eng_edf, reqs, tolerate_errors=True)
    sides = [dict(eng=eng_fifo, name="fifo", good=[], missed=[], shed=0,
                  hung=0),
             dict(eng=eng_edf, name="edf", good=[], missed=[], shed=0,
                  hung=0)]
    ratios = []
    for _ in range(OVERLOAD_ROUNDS):
        round_good = [0, 0]
        for i, s in enumerate(sides):
            m0 = s["eng"].metrics()
            r = run_workload_async(s["eng"], reqs, tolerate_errors=True)
            m1 = s["eng"].metrics()
            s["hung"] += r["hung"]
            g = int(m1.get("goodput_interactive", 0)
                    - m0.get("goodput_interactive", 0))
            s["good"].append(g)
            s["missed"].append(int(
                m1.get("deadline_misses_interactive", 0)
                - m0.get("deadline_misses_interactive", 0)))
            round_good[i] = g
        ratios.append((round_good[1] + 1) / (round_good[0] + 1))
    summary = {}
    for s in sides:
        m = s["eng"].metrics()
        summary[s["name"]] = {
            "goodput_interactive_per_round": s["good"],
            "misses_interactive_per_round": s["missed"],
            "goodput_interactive": int(sum(s["good"])),
            "shed_total": int(m.get("shed_total", 0)),
            "shed_bulk": int(m.get("shed_bulk", 0)),
            "shed_standard": int(m.get("shed_standard", 0)),
            "shed_interactive": int(m.get("shed_interactive", 0)),
            "hung": s["hung"],
        }
        s["eng"].shutdown()
    goodput_ratio = float(np.median(ratios))
    print(f"{'policy':<22}{'good(int)':>10}{'miss(int)':>10}{'shed':>7}")
    for name in ("fifo", "edf"):
        r = summary[name]
        print(f"{name:<22}{r['goodput_interactive']:>10}"
              f"{sum(r['misses_interactive_per_round']):>10}"
              f"{r['shed_total']:>7}")
    print(f"-> EDF+shed: interactive goodput-under-SLO x{goodput_ratio:.2f} "
          f"median per-round vs FIFO (per-round "
          f"{[round(r, 2) for r in ratios]}); EDF shed "
          f"{summary['edf']['shed_total']} low-priority requests to get "
          f"there; hung futures fifo={summary['fifo']['hung']} "
          f"edf={summary['edf']['hung']}")

    # ---- chaos pass: injected faults must never hang a future ----
    faults = FaultInjector.parse(OVERLOAD_FAULT_SPEC, seed=41)
    eng_chaos = overload_engine(
        "edf", "tiered", faults=faults,
        degradation=DegradationPolicy(threshold_s=0.05),
        watchdog=OVERLOAD_WATCHDOG_GRACE_S)
    chaos_hung = 0
    chaos = {}
    for _ in range(2):
        r = run_workload_async(eng_chaos, reqs, tolerate_errors=True)
        chaos_hung += r["hung"]
        chaos = {k: r[k] for k in
                 ("resolved", "rejected", "failed", "hung")}
    mc = eng_chaos.metrics()
    chaos.update(
        hung_total=chaos_hung,
        fault_dispatch_fired=int(mc.get("fault_dispatch_fired", 0)),
        fault_stall_fired=int(mc.get("fault_stall_fired", 0)),
        fault_evict_fired=int(mc.get("fault_evict_fired", 0)),
        dispatch_retries=int(mc.get("dso_dispatch_retries", 0)),
        dispatch_failures=int(mc.get("dso_dispatch_failures", 0)),
        watchdog_timeouts=int(mc.get("watchdog_timeouts", 0)),
        encode_recoveries=int(mc.get("encode_recoveries", 0)),
        degrade_steps=int(mc.get("degrade_steps", 0)))
    eng_chaos.shutdown()
    print(f"-> chaos ({OVERLOAD_FAULT_SPEC}): "
          f"{chaos['fault_dispatch_fired']} dispatch faults "
          f"({chaos['dispatch_retries']} retried, "
          f"{chaos['dispatch_failures']} fatal), "
          f"{chaos['fault_stall_fired']} stalls, "
          f"{chaos['fault_evict_fired']} eviction storms, "
          f"{chaos['watchdog_timeouts']} watchdog fails; "
          f"hung futures: {chaos_hung}")
    if csv:
        print(f"serving/overload_fifo,0,"
              f"goodput_int={summary['fifo']['goodput_interactive']}")
        print(f"serving/overload_edf,0,"
              f"goodput_int={summary['edf']['goodput_interactive']}")

    total_hung = (summary['fifo']['hung'] + summary['edf']['hung']
                  + chaos_hung)
    if total_hung:
        raise AssertionError(
            f"{total_hung} future(s) never resolved — the zero-hung "
            f"liveness gate failed")
    if goodput_ratio < OVERLOAD_GOODPUT_MIN:
        raise AssertionError(
            f"EDF+shed interactive goodput x{goodput_ratio:.2f} < "
            f"{OVERLOAD_GOODPUT_MIN} vs FIFO (per-round ratios "
            f"{[round(r, 2) for r in ratios]}) — overload gate failed")
    if chaos["fault_dispatch_fired"] < 1 or chaos["fault_evict_fired"] < 1:
        raise AssertionError(
            "chaos pass fired no dispatch/evict faults — the injector is "
            "not engaging (seed/spec drift?)")
    return {
        "workload": {"distribution": "lognormal",
                     "counts": list(OVERLOAD_COUNTS),
                     "n_requests": OVERLOAD_REQUESTS,
                     "history": OVERLOAD_HISTORY, "n_users": REPEAT_USERS,
                     "tier_mix": dict(OVERLOAD_TIER_MIX),
                     "tier_slo_s": dict(OVERLOAD_TIER_SLO),
                     "n_workers": OVERLOAD_WORKERS,
                     "max_pending": OVERLOAD_PENDING,
                     "cpu_count": int(os.cpu_count() or 1)},
        "fifo": summary["fifo"],
        "edf": summary["edf"],
        "goodput_ratio_median_per_round": goodput_ratio,
        "per_round_ratios": [float(r) for r in ratios],
        "chaos": dict(chaos, fault_spec=OVERLOAD_FAULT_SPEC),
        "gates": {"overload_goodput_min": OVERLOAD_GOODPUT_MIN,
                  "zero_hung_futures": True,
                  "chaos_faults_fired": True},
    }


def _merge_report(section: str, payload: dict):
    """Update one section of BENCH_serving.json in place (standalone
    profile runs must not clobber the other profiles' trajectory)."""
    path = os.path.abspath(OUT_PATH)
    report = {}
    if os.path.exists(path):
        with open(path) as f:
            report = json.load(f)
    report[section] = payload
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {path} ({section})")


#: standalone profile name -> runner.  scripts/check_docs.py parses this
#: dict (by AST, without importing jax) to verify every `--profile <name>`
#: mentioned in benchmarks/README.md actually exists; add new profiles here.
PROFILE_RUNNERS = {
    "fke": run_fke_profile,
    "dso_nonuniform": run_dso_nonuniform_profile,
    "sharded": run_sharded_profile,
    "decode": run_decode_profile,
    "decode_fused": run_decode_fused_profile,
    "overload": run_overload_profile,
}


def main(csv=True, profile: str = "all"):
    enable_compile_cache()
    cfg, bundle, params = make_climber(d_model=64, layers=2, blocks=2)
    if profile in PROFILE_RUNNERS:
        _merge_report(profile, PROFILE_RUNNERS[profile](bundle, params, csv))
        return
    tc = TrafficConfig(candidate_counts=COUNTS, distribution="jittered",
                       n_requests=N_REQUESTS, n_history=HISTORY, seed=11)
    reqs = generate_traffic(tc, n_items=N_ITEMS)

    print("\n=== Serving API v2: coalesced vs per-request dispatch "
          "(jittered traffic, hot cache) ===")
    base, out_base, _ = _run(bundle, params, reqs, coalesce=False,
                             sequential_ref=False)
    coal, out_coal, seq_ref = _run(bundle, params, reqs, coalesce=True,
                                   sequential_ref=True)

    bitwise_seq = all(np.array_equal(a, b)
                      for a, b in zip(seq_ref, out_coal))
    bitwise_base = all(np.array_equal(a, b)
                       for a, b in zip(out_base, out_coal))
    print(f"{'config':<26}{'items/s':>10}{'p50 ms':>9}{'p99 ms':>9}"
          f"{'dispatches':>12}{'fill':>6}")
    for name, r in (("per-request (B=1)", base),
                    (f"coalesced (B={MAX_BATCH})", coal)):
        print(f"{name:<26}{r['throughput_items_per_s']:>10.0f}"
              f"{r['p50_latency_ms']:>9.1f}{r['p99_latency_ms']:>9.1f}"
              f"{r['dispatches']:>12}{r['avg_fill']:>6.1f}")
    speedup = (coal["throughput_items_per_s"]
               / max(base["throughput_items_per_s"], 1e-9))
    print(f"-> coalescing: throughput x{speedup:.2f}; bitwise vs sequential "
          f"self: {bitwise_seq}; bitwise vs B=1 baseline: {bitwise_base}")
    if csv:
        print(f"serving/uncoalesced,{base['p50_latency_ms'] * 1e3:.1f},"
              f"tput={base['throughput_items_per_s']:.0f}")
        print(f"serving/coalesced,{coal['p50_latency_ms'] * 1e3:.1f},"
              f"tput={coal['throughput_items_per_s']:.0f}")

    print("\n=== History-KV pool: repeat-user / session re-rank "
          f"({REPEAT_USERS} users, history {REPEAT_HISTORY}, hot pool) ===")
    rtc = TrafficConfig(candidate_counts=REPEAT_COUNTS,
                        distribution="jittered",
                        n_requests=N_REQUESTS, n_history=REPEAT_HISTORY,
                        seed=13, n_users=REPEAT_USERS)
    rreqs = generate_traffic(rtc, n_items=N_ITEMS)
    eng_full = _repeat_engine(bundle, params, history_cache=False)
    eng_pool = _repeat_engine(bundle, params, history_cache=True,
                              pool_budget_bytes=V2_BUDGET_BYTES)
    full, out_full, pooled, out_pool = _ab_interleaved(eng_full, eng_pool,
                                                       rreqs)
    eng_full.shutdown()
    bitwise_frac = np.mean([np.array_equal(a, b)
                            for a, b in zip(out_full, out_pool)])
    pool_max_diff = max(
        float(np.abs(a.astype(np.float32) - b.astype(np.float32)).max())
        for a, b in zip(out_full, out_pool))
    print(f"{'config':<26}{'items/s':>10}{'p50 ms':>9}{'p99 ms':>9}"
          f"{'hits':>6}{'miss':>6}")
    for name, r in (("full pass (pool off)", full),
                    ("history pool (hot)", pooled)):
        print(f"{name:<26}{r['throughput_items_per_s']:>10.0f}"
              f"{r['p50_latency_ms']:>9.1f}{r['p99_latency_ms']:>9.1f}"
              f"{r['pool_hits']:>6}{r['pool_misses']:>6}")
    pool_speedup = (pooled["throughput_items_per_s"]
                    / max(full["throughput_items_per_s"], 1e-9))
    print(f"-> history pool: throughput x{pool_speedup:.2f}; vs full pass: "
          f"max |diff| {pool_max_diff:.2e}, bitwise on "
          f"{bitwise_frac:.0%} of requests; "
          f"pool bytes {pooled['pool_bytes']}")
    if csv:
        print(f"serving/repeat_full,{full['p50_latency_ms'] * 1e3:.1f},"
              f"tput={full['throughput_items_per_s']:.0f}")
        print(f"serving/repeat_pooled,{pooled['p50_latency_ms'] * 1e3:.1f},"
              f"tput={pooled['throughput_items_per_s']:.0f}")

    print("\n=== PDA v2: device-resident, byte-budgeted pool vs PR 2-style "
          "host pool (hot repeat-user path) ===")
    eng_v1 = _repeat_engine(bundle, params, history_cache=True,
                            pool_placement="host", kv_dedup=False)
    v1_style, out_v1, v2, out_v2 = _ab_interleaved(eng_v1, eng_pool, rreqs)
    eng_v1.shutdown()
    v2_speedup = (v2["throughput_items_per_s"]
                  / max(v1_style["throughput_items_per_s"], 1e-9))
    v2_max_diff = max(
        float(np.abs(a.astype(np.float32) - b.astype(np.float32)).max())
        for a, b in zip(out_v1, out_v2))
    # KV-row dedup exercised explicitly: auto-dedup resolves OFF on the CPU
    # backend (stacking is a local memcpy; the executor gather would be
    # pure overhead) and ON for accelerators, where each deduped row is a
    # skipped host->HBM transfer.  Recorded, not wall-clock-gated on CPU.
    eng_dd = _repeat_engine(bundle, params, history_cache=True,
                            kv_dedup=True)
    run_workload_async(eng_dd, rreqs)
    m0 = eng_dd.metrics()
    rdd = run_workload_async(eng_dd, rreqs)
    rdd.pop("outputs")
    forced = dict(rdd, **_pool_delta(m0, eng_dd.metrics()))
    row_bytes = forced["pool_bytes"] // max(len(eng_dd.history_pool), 1)
    forced["transfer_bytes_saved_per_pass"] = \
        forced["dedup_rows_saved"] * row_bytes
    eng_dd.shutdown()
    print(f"{'config':<28}{'items/s':>10}{'p50 ms':>9}{'p99 ms':>9}"
          f"{'dedup':>7}")
    for name, r in (("v1-style (host, no dedup)", v1_style),
                    ("PDA v2 (device + budget)", v2),
                    ("PDA v2 + forced dedup", forced)):
        print(f"{name:<28}{r['throughput_items_per_s']:>10.0f}"
              f"{r['p50_latency_ms']:>9.1f}{r['p99_latency_ms']:>9.1f}"
              f"{r['dedup_rows_saved']:>7}")
    print(f"-> PDA v2: throughput x{v2_speedup:.2f} vs v1-style pool "
          f"(CPU backend: placements coincide, so this is a parity guard; "
          f"the dedup row saves {forced['dedup_rows_saved']} restacks "
          f"= {forced['transfer_bytes_saved_per_pass'] / 1e6:.1f} MB of "
          f"per-pass H2D on an accelerator); max |diff| {v2_max_diff:.2e}")
    if csv:
        print(f"serving/pool_v1_style,{v1_style['p50_latency_ms'] * 1e3:.1f},"
              f"tput={v1_style['throughput_items_per_s']:.0f}")
        print(f"serving/pool_v2,{v2['p50_latency_ms'] * 1e3:.1f},"
              f"tput={v2['throughput_items_per_s']:.0f}")

    print("\n=== Suffix extension: stale-sweep (tail-append) traffic, "
          "full re-encode vs incremental ===")
    reenc, out_re, ext, out_ext = _run_stale_sweeps_interleaved(bundle,
                                                                params)
    ext_speedup = (ext["throughput_items_per_s"]
                   / max(reenc["throughput_items_per_s"], 1e-9))
    ext_max_diff = max(
        float(np.abs(a.astype(np.float32) - b.astype(np.float32)).max())
        for a, b in zip(out_re, out_ext))
    print(f"{'config':<26}{'items/s':>10}{'p50 ms':>9}{'p99 ms':>9}"
          f"{'stale':>7}{'ext':>5}")
    for name, r in (("full re-encode", reenc),
                    ("suffix extension", ext)):
        print(f"{name:<26}{r['throughput_items_per_s']:>10.0f}"
              f"{r['p50_latency_ms']:>9.1f}{r['p99_latency_ms']:>9.1f}"
              f"{r['pool_stale']:>7}{r['pool_extensions']:>5}")
    print(f"-> suffix extension: throughput x{ext_speedup:.2f} on stale "
          f"hits; max |diff| vs re-encode {ext_max_diff:.2e}")
    if csv:
        print(f"serving/stale_reencode,{reenc['p50_latency_ms'] * 1e3:.1f},"
              f"tput={reenc['throughput_items_per_s']:.0f}")
        print(f"serving/stale_extend,{ext['p50_latency_ms'] * 1e3:.1f},"
              f"tput={ext['throughput_items_per_s']:.0f}")

    print("\n=== Quantized pool: int8 entries vs native "
          "(hot repeat-user path) ===")
    eng_q8 = _repeat_engine(bundle, params, history_cache=True,
                            pool_dtype="int8")
    v2_again, _, q8, out_q8 = _ab_interleaved(eng_pool, eng_q8, rreqs)
    eng_pool.shutdown()
    eng_q8.shutdown()
    q8_speedup = (q8["throughput_items_per_s"]
                  / max(v2_again["throughput_items_per_s"], 1e-9))
    q8_drift = max(
        float(np.abs(a.astype(np.float32) - b.astype(np.float32)).max())
        for a, b in zip(out_v2, out_q8))
    bytes_ratio = q8["pool_bytes"] / max(v2["pool_bytes"], 1)
    print(f"int8 pool: {q8['throughput_items_per_s']:.0f} items/s "
          f"(x{q8_speedup:.2f} vs native), bytes/entry ratio "
          f"{bytes_ratio:.2f}, score drift {q8_drift:.2e} "
          f"(~{1 / max(bytes_ratio, 1e-9):.1f}x users per byte budget)")
    if csv:
        print(f"serving/pool_int8,{q8['p50_latency_ms'] * 1e3:.1f},"
              f"tput={q8['throughput_items_per_s']:.0f}")

    fke = run_fke_profile(bundle, params, csv)
    dso_nonuniform = run_dso_nonuniform_profile(bundle, params, csv)
    sharded = run_sharded_profile(bundle, params, csv)
    decode = run_decode_profile(bundle, params, csv)
    decode_fused = run_decode_fused_profile(bundle, params, csv)
    overload = run_overload_profile(bundle, params, csv)

    report = {
        "workload": {"distribution": "jittered", "counts": list(COUNTS),
                     "n_requests": N_REQUESTS, "history": HISTORY,
                     "buckets": list(BUCKETS), "max_batch": MAX_BATCH,
                     "n_workers": N_WORKERS},
        "uncoalesced": base,
        "coalesced": coal,
        "speedup_items_per_s": speedup,
        "bitwise_identical": bool(bitwise_base),
        "bitwise_vs_sequential_self": bool(bitwise_seq),
        "repeat_user": {
            "workload": {"distribution": "jittered",
                         "counts": list(REPEAT_COUNTS),
                         "n_requests": N_REQUESTS, "history": REPEAT_HISTORY,
                         "n_users": REPEAT_USERS, "pool_slots": POOL_SLOTS,
                         "max_batch": REPEAT_MAX_BATCH},
            "full_pass": full,
            "history_pool": pooled,
            "speedup_items_per_s": pool_speedup,
            "max_abs_diff_vs_full": pool_max_diff,
            "bitwise_fraction": float(bitwise_frac),
        },
        "pda_v2": {
            "v1_style_pool": v1_style,
            "v2_pool": v2,
            "forced_dedup": forced,
            "speedup_items_per_s": v2_speedup,
            "max_abs_diff_vs_v1": v2_max_diff,
        },
        "suffix_extension": {
            "workload": {"n_sweeps": 16, "n_users": REPEAT_USERS,
                         "history": STALE_HISTORY, "tail_append": 4},
            "full_reencode": reenc,
            "incremental": ext,
            "speedup_items_per_s": ext_speedup,
            "max_abs_diff_vs_reencode": ext_max_diff,
        },
        "quantized_pool": {
            "int8": q8,
            "items_per_s_vs_native": q8_speedup,
            "bytes_ratio_vs_native": bytes_ratio,
            "max_score_drift_vs_native": q8_drift,
        },
        "fke": fke,
        "dso_nonuniform": dso_nonuniform,
        "sharded": sharded,
        "decode": decode,
        "decode_fused": decode_fused,
        "overload": overload,
        "gates": {
            "coalesced_bitwise": True,
            "pool_tolerance": 2e-3,
            "pool_speedup_min": 1.5,
            "pda_v2_speedup_min": 0.9,
            "extension_speedup_min": 1.1,
            "int8_drift_max": 5e-2,
            "fke_speedup_min": FKE_SPEEDUP_MIN,
            "dso_pack_speedup_min": DSO_SPEEDUP_MIN,
            "dso_pad_ratio_min": DSO_PAD_RATIO_MIN,
            "sharded_parity_min": SHARDED_PARITY_MIN,
            "sharded_tolerance": SHARDED_TOL,
            "decode_speedup_min": DECODE_SPEEDUP_MIN,
            "decode_fused_speedup_min": DECODE_FUSED_SPEEDUP_MIN,
            "overload_goodput_min": OVERLOAD_GOODPUT_MIN,
        },
    }
    path = os.path.abspath(OUT_PATH)
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {path}")
    if not (bitwise_seq and bitwise_base):
        raise AssertionError("coalesced scores diverged from per-request "
                             "reference — correctness gate failed")
    if pool_max_diff > 2e-3:
        raise AssertionError(
            f"pooled-history scores diverged from the full pass by "
            f"{pool_max_diff:.2e} (> 2e-3) — correctness gate failed")
    if pool_speedup < 1.5:
        raise AssertionError(
            f"history pool speedup x{pool_speedup:.2f} < 1.5 on the "
            f"repeat-user profile — perf gate failed")
    if v2_max_diff > 2e-3 or ext_max_diff > 2e-3:
        raise AssertionError(
            f"PDA v2 / suffix-extension scores diverged (v2 "
            f"{v2_max_diff:.2e}, ext {ext_max_diff:.2e} vs 2e-3 gate)")
    if v2_speedup < 0.9:
        raise AssertionError(
            f"PDA v2 x{v2_speedup:.2f} vs the v1-style pool — parity "
            f"guard failed (v2 machinery must be free on CPU)")
    if forced["dedup_rows_saved"] < 1:
        raise AssertionError(
            "forced-dedup run saved no KV-row restacks — dedup machinery "
            "is not engaging on multi-chunk traffic")
    if ext_speedup < 1.1:
        raise AssertionError(
            f"suffix extension x{ext_speedup:.2f} < 1.1 vs full re-encode "
            f"on stale sweeps — perf gate failed")
    if q8_drift > 5e-2:
        raise AssertionError(
            f"int8 pool score drift {q8_drift:.2e} exceeds the 5e-2 bound")
    return report


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--profile", default="all",
                    choices=["all"] + sorted(PROFILE_RUNNERS),
                    help="'fke' runs only the fused-engine A/B + gates; "
                         "'dso_nonuniform' runs only the segment-packing "
                         "vs PR-4-coalescing A/B + gates; 'decode' runs "
                         "only the packed-vs-unpacked generative decode "
                         "A/B + gates (all CI gates); each merges its "
                         "section into BENCH_serving.json")
    main(profile=ap.parse_args().profile)
