"""Host-path instrumentation: every serving stage opens a ``flame.*``
profiler span and adds its seconds to counters in ``eng.metrics()``, and
every executor carries a stable name.

One tiny FlameEngine (history pool, packed tails, incremental history)
serves a miss, a hit, a grown history and a few concurrent requests under
the profiler, inside a marker annotation as the benchmark marks its
window; the tests read the trace back with ``ProfileData``.
"""
import dataclasses
import glob
import re
import tempfile

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.pda import RemoteFeatureStore
from repro.models import build_model
from repro.serving import FlameEngine, ServeRequest
from repro.types import ClimberConfig

MARKER = "test.window"
N_HISTORY = 64
EXECUTORS = [("cached", 32), ("cached", 16), ("encode", N_HISTORY),
             ("extend", N_HISTORY)]
#: span -> the metadata keys it carries
SPANS = {
    "flame.admit": {"request_id"},
    "flame.pda.features": {"request_id"},
    "flame.pool.lookup": set(),
    "flame.pool.put": set(),
    "flame.dso.stack": {"kind", "bucket", "rows"},
    "flame.dso.launch": {"kind", "bucket", "rows"},
    "flame.dso.readback": {"kind", "bucket", "rows"},
    "flame.dso.scatter": {"kind", "bucket", "rows"},
}
COUNTERS = [
    "admit_s", "admit_n", "features_s", "features_n",
    "pool_lookup_s", "pool_lookup_n", "pool_put_s", "pool_put_n",
    "dso_queue_delay_s", "dso_queue_delay_n",
    "dso_stack_s", "dso_launch_s", "dso_wait_s", "dso_readback_s",
    "dso_scatter_s", "service_s", "service_n",
    "dso_run_s_cached", "dso_run_s_encode", "dso_run_s_extend",
]


@pytest.fixture(scope="module")
def traced():
    cfg = dataclasses.replace(
        get_config("climber"), vocab_size=10_000, d_model=64, d_ff=128,
        n_heads=2, n_kv_heads=2, head_dim=32,
        climber=ClimberConfig(num_blocks=2, layers_per_block=2))
    bundle = build_model(cfg)
    params, _ = bundle.init(jax.random.key(0))
    eng = FlameEngine(
        bundle, params, n_history=N_HISTORY, buckets=(32, 16), n_streams=2,
        feature_mode="sync",
        store=RemoteFeatureStore(latency_s=0.0, feature_dim=12),
        window_s=0.005, max_batch=4, n_workers=4, history_cache=True,
        pool_slots=32, pool_dtype="int8", pack_tails=True,
        incremental_history=True, extend_buckets=(N_HISTORY,))
    rng = np.random.default_rng(0)
    hist = rng.integers(0, 10_000, N_HISTORY + 8).astype(np.int32)
    grown = np.concatenate([hist, rng.integers(0, 10_000, 4)
                            ]).astype(np.int32)

    def cands(m):
        return rng.integers(0, 10_000, m).astype(np.int32)

    out_dir = tempfile.mkdtemp(prefix="flame-tracing-test-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    try:
        before = eng.metrics()
        with jax.profiler.TraceAnnotation(MARKER):
            eng.serve(hist, cands(40), user_id=0)       # miss: encode
            eng.serve(hist, cands(9), user_id=0)        # hit
            eng.serve(grown, cands(20), user_id=0)      # grown: extend
            futs = [eng.submit(ServeRequest(
                history=rng.integers(0, 10_000, N_HISTORY).astype(np.int32),
                candidates=cands(5 + 7 * u), user_id=10 + u))
                for u in range(4)]
            for f in futs:
                f.result()
        after = eng.metrics()
    finally:
        jax.profiler.stop_trace()
    eng.shutdown()
    final = eng.metrics()
    path = max(glob.glob(f"{out_dir}/**/*.xplane.pb", recursive=True))
    return {"eng": eng, "before": before, "after": after, "final": final,
            "events": _host_events(path)}


def _host_events(path):
    """(name, stats, start_ns, end_ns) of every host event inside the
    marker annotation."""
    from jax._src.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host = [p for p in pd.planes if p.name == "/host:CPU"]
    assert host, [p.name for p in pd.planes]
    evs = [(ev.name, dict(ev.stats), ev.start_ns,
            ev.start_ns + ev.duration_ns)
           for line in host[0].lines for ev in line.events]
    marks = [e for e in evs if e[0] == MARKER]
    assert len(marks) == 1, [e[0] for e in evs][:50]
    _, _, w0, w1 = marks[0]
    return [e for e in evs if w0 <= e[2] and e[3] <= w1 and e[0] != MARKER]


@pytest.mark.parametrize("span", sorted(SPANS))
def test_span_on_host_plane_under_bare_name(traced, span):
    got = [e for e in traced["events"] if e[0] == span]
    assert got, sorted({e[0] for e in traced["events"]
                        if e[0].startswith("flame")})
    for _, stats, _, _ in got:
        assert SPANS[span] <= set(stats), (span, stats)
    # metadata rides as stats, never in the name
    assert not [e[0] for e in traced["events"]
                if e[0].startswith("flame.") and e[0] not in SPANS]


def test_launch_spans_match_dispatches(traced):
    launches = [e for e in traced["events"] if e[0] == "flame.dso.launch"]
    delta = traced["after"]["dso_dispatches"] \
        - traced["before"]["dso_dispatches"]
    assert delta > 0
    assert len(launches) == delta


@pytest.mark.parametrize("key", COUNTERS)
def test_counter_present_and_grows(traced, key):
    before, after, final = (traced[k] for k in ("before", "after", "final"))
    assert key in before and key in after and key in final
    assert before[key] <= after[key] <= final[key]
    assert final[key] > before[key]


def test_stage_counts_agree(traced):
    before, final = traced["before"], traced["final"]
    d = {k: final[k] - before.get(k, 0) for k in final}
    requests = 7
    assert d["admit_n"] == d["service_n"] == requests
    assert d["pool_lookup_n"] == requests
    # misses and the grown history query features and put an entry
    assert d["features_n"] == d["pool_put_n"] == d["pool_misses"]
    assert d["dso_queue_delay_n"] == d["dso_rows_dispatched"]
    run = sum(d[f"dso_run_s_{k}"] for k in ("cached", "encode", "extend"))
    assert run == pytest.approx(d["dso_launch_s"] + d["dso_wait_s"])


@pytest.mark.parametrize("kind,bucket", EXECUTORS)
def test_executor_module_named(traced, kind, bucket):
    text = traced["eng"].dso.compiled[(kind, bucket)].as_text()
    assert re.match(rf"HloModule jit_flame_{kind}_b{bucket}\b", text), \
        text.splitlines()[0]


def test_host_events_name_executors(traced):
    calls = {e[0] for e in traced["events"]
             if e[0].startswith("PjitFunction(jit(flame_")}
    want = {f"PjitFunction(jit(flame_{k}_b{b}))"
            for k, b in EXECUTORS}
    assert calls <= want
    assert {c.split("_")[1] for c in calls} == {"cached", "encode",
                                                 "extend"}
    assert not [e for e in traced["events"]
                if e[0] == "PjitFunction(jit(fn))"]


def test_dropped_gauges_absent(traced):
    m = traced["final"]
    assert "queue_delay_ms" not in m and "gen_tokens_per_s" not in m
    assert "dso_queue_delay_ms" in m


def test_stage_counters_lose_no_update_across_threads():
    """More threads than cores add stage times to one ServeMetrics and
    probe one pool, with a short switch interval: every call counts."""
    import sys
    import threading

    from repro.serving import HistoryKVPool, ServeMetrics

    metrics = ServeMetrics(stages=("features",))
    pool = HistoryKVPool(8)
    pool.put(("u", 0), "fp", {"k": np.zeros((1, 2), np.float32)})
    n_threads, n_calls = 16, 300

    def work():
        for _ in range(n_calls):
            metrics.add_time("features", 0.001)
            pool.lookup(("u", 0), "fp")

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in threads)
    total = n_threads * n_calls
    summary = metrics.summary()
    assert summary["features_n"] == total
    assert summary["features_s"] == pytest.approx(0.001 * total)
    stats = pool.stats()
    assert stats["lookup_n"] == stats["hits"] == total
    assert stats["put_n"] == 1 and stats["lookup_s"] > 0
