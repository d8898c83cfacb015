"""API v2: registry, pipeline, backpressure, and the coalescing DSO's two
contract guarantees — bitwise-identical scores and fewer dispatches."""
import dataclasses
import threading

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.core.pda import RemoteFeatureStore
from repro.models import build_model
from repro.serving import (AdmissionQueueFull, FlameEngine, ServeMetrics,
                           ServeRequest, ServingEngine, available_engines,
                           create_engine)
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
                feature_mode="off", store=_store(), window_s=0.05)
    base.update(kw)
    return FlameEngine(bundle, params, **base)


def test_registry_names_and_unknown():
    assert {"flame", "text"} <= set(available_engines())
    with pytest.raises(KeyError, match="unknown engine"):
        create_engine("nope")


def test_engines_satisfy_protocol(climber_setup):
    cfg, bundle, params = climber_setup
    eng = create_engine("flame", bundle, params, n_history=64,
                        buckets=(16,), feature_mode="off", store=_store())
    assert isinstance(eng, ServingEngine)
    eng.shutdown()


def test_submit_returns_future_with_response(climber_setup):
    cfg, bundle, params = climber_setup
    eng = _flame(bundle, params)
    rng = np.random.default_rng(0)
    req = ServeRequest(history=rng.integers(0, 1000, 64).astype(np.int32),
                       candidates=rng.integers(0, 1000, 24).astype(np.int32))
    fut = eng.submit(req)
    resp = fut.result(timeout=60)
    assert resp.request_id == req.request_id
    assert resp.output.shape == (24, 3)
    assert resp.latency_s > 0
    assert {"queue_s", "features_s", "execute_s"} <= set(resp.timings)
    m = eng.metrics()
    assert m["requests"] == 1 and m["dso_chunks"] == 2
    eng.shutdown()


def test_coalesced_concurrent_bitwise_matches_sequential(climber_setup):
    """The tentpole correctness contract: scores under concurrent jittered
    traffic (chunks coalesced across requests) are bitwise-identical to the
    same engine serving the same requests one at a time."""
    cfg, bundle, params = climber_setup
    eng = _flame(bundle, params, coalesce=True, max_batch=4, n_workers=4)
    tc = TrafficConfig(candidate_counts=(16, 32, 64), distribution="jittered",
                       n_requests=12, n_history=64, seed=7)
    reqs = generate_traffic(tc, n_items=10_000)
    sequential = [eng.serve(r["history"], r["candidates"]) for r in reqs]
    concurrent = run_workload_async(eng, reqs)["outputs"]
    for s, c in zip(sequential, concurrent):
        np.testing.assert_array_equal(s, c)
    eng.shutdown()


def test_coalescing_reduces_dispatch_count(climber_setup):
    """16 single-chunk requests (M == smallest bucket) in flight together:
    with coalescing the dispatcher must merge chunks from different requests
    (dispatches < chunks); without it, every chunk dispatches alone."""
    cfg, bundle, params = climber_setup
    rng = np.random.default_rng(3)
    reqs = [{"history": rng.integers(0, 1000, 64).astype(np.int32),
             "candidates": rng.integers(0, 1000, 16).astype(np.int32)}
            for _ in range(16)]

    on = _flame(bundle, params, buckets=(16,), coalesce=True, max_batch=4,
                n_workers=4)
    run_workload_async(on, reqs)
    m_on = on.metrics()
    on.shutdown()
    assert m_on["dso_chunks"] == 16
    assert m_on["dso_dispatches"] < m_on["dso_chunks"]
    assert m_on["dso_avg_fill"] > 1.0

    off = _flame(bundle, params, buckets=(16,), coalesce=False, n_workers=4)
    run_workload_async(off, reqs)
    m_off = off.metrics()
    off.shutdown()
    assert m_off["dso_dispatches"] == m_off["dso_chunks"] == 16
    assert m_off["dso_batch_axis"] == 1


def test_admission_queue_backpressure(climber_setup):
    """n_workers=0 never drains: the bounded queue must fill and submit
    must raise AdmissionQueueFull instead of growing without bound."""
    cfg, bundle, params = climber_setup
    eng = _flame(bundle, params, buckets=(16,), max_pending=2, n_workers=0)
    rng = np.random.default_rng(0)

    def req():
        return ServeRequest(
            history=rng.integers(0, 1000, 64).astype(np.int32),
            candidates=rng.integers(0, 1000, 16).astype(np.int32))

    eng.submit(req(), timeout=0)
    eng.submit(req(), timeout=0)
    with pytest.raises(AdmissionQueueFull):
        eng.submit(req(), timeout=0)
    assert eng.metrics()["pending"] == 2
    eng.shutdown()


def test_malformed_request_fails_alone(climber_setup):
    """A bad-shape request must fail through its own future *before* its
    chunks reach the shared coalescing queue — co-riding healthy requests
    must be unaffected."""
    cfg, bundle, params = climber_setup
    eng = _flame(bundle, params, n_workers=2)
    rng = np.random.default_rng(5)
    bad = ServeRequest(history=rng.integers(0, 1000, 10).astype(np.int32),
                       candidates=rng.integers(0, 1000, 16).astype(np.int32))
    good = ServeRequest(history=rng.integers(0, 1000, 64).astype(np.int32),
                        candidates=rng.integers(0, 1000, 16).astype(np.int32))
    fb, fg = eng.submit(bad), eng.submit(good)
    with pytest.raises(ValueError, match="history"):
        fb.result(timeout=60)
    assert fg.result(timeout=60).output.shape == (16, 3)
    with pytest.raises(ValueError, match="candidates"):
        eng.submit(ServeRequest(
            history=rng.integers(0, 1000, 64).astype(np.int32),
            candidates=None)).result(timeout=60)
    eng.shutdown()


def test_text_engine_submit_matches_generate():
    cfg = reduced_config("h2o-danube-3-4b")
    bundle = build_model(cfg)
    params, _ = bundle.init(jax.random.key(0))
    eng = create_engine("text", bundle, params, batch=2, max_len=64)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, 10).astype(np.int32)
    direct = eng.generate([prompt], n_tokens=4)[0]
    resp = eng.submit(ServeRequest(history=prompt, n_tokens=4)).result(
        timeout=120)
    np.testing.assert_array_equal(resp.output, direct)
    assert eng.metrics()["requests"] == 1
    eng.shutdown()


def test_serve_metrics_record_is_thread_safe():
    m = ServeMetrics()
    n_threads, per_thread = 8, 200

    def hammer():
        for _ in range(per_thread):
            m.record(2, 0.001)

    threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    s = m.summary()
    assert s["requests"] == n_threads * per_thread
    assert m.items == 2 * n_threads * per_thread
    assert len(m.latencies) == n_threads * per_thread
