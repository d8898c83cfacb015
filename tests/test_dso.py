"""DSO: bucket routing properties (hypothesis) + executor pool behaviour."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tests._propcheck import given, settings, st

from repro.core.dso import (Chunk, DynamicStreamOrchestrator, ExecutorPool,
                            padded_fraction, split_request)

BUCKETS = st.lists(st.sampled_from([16, 32, 64, 128, 256, 512, 1024]),
                   min_size=1, max_size=5, unique=True)


@given(st.integers(1, 5000), BUCKETS)
@settings(max_examples=200, deadline=None)
def test_split_request_properties(m, buckets):
    plan = split_request(m, buckets)
    # 1. covers every candidate exactly once, in order
    assert plan[0].start == 0
    for a, b in zip(plan, plan[1:]):
        assert b.start == a.start + a.valid
    assert plan[-1].start + plan[-1].valid == m
    # 2. every chunk runs on a real bucket, valid <= bucket
    for c in plan:
        assert c.bucket in buckets and 1 <= c.valid <= c.bucket
    # 3. only the LAST chunk may be padded
    for c in plan[:-1]:
        assert c.valid == c.bucket
    # 4. greedy-descending: bucket sizes never increase along the plan
    sizes = [c.bucket for c in plan]
    assert sizes == sorted(sizes, reverse=True)
    # 5. padding bounded by smallest bucket
    pad = sum(c.bucket for c in plan) - m
    assert pad < min(buckets)


def test_padded_fraction():
    assert padded_fraction(128, [128]) == 0.0
    assert padded_fraction(1, [128]) > 0.99


def _build_pool(buckets, n_streams=2):
    def build_fn(bucket):
        def fn(x):
            return x * 2.0
        return jax.jit(fn).lower(
            jax.ShapeDtypeStruct((1, bucket), jnp.float32)).compile()
    return ExecutorPool(build_fn, buckets, n_streams=n_streams)


def test_executor_pool_checkout():
    pool = _build_pool([32, 16])
    e1 = pool.acquire(32)
    e2 = pool.acquire(32)
    assert e1.eid != e2.eid
    pool.release(e1)
    e3 = pool.acquire(32)
    assert e3.eid == e1.eid       # round-trips through the index queue


def test_orchestrator_end_to_end_matches_direct():
    pool = _build_pool([32, 16], n_streams=2)

    def pad_slice(request, chunk: Chunk):
        x, = request
        sl = x[:, chunk.start:chunk.start + chunk.valid]
        if chunk.valid < chunk.bucket:
            sl = jnp.pad(sl, ((0, 0), (0, chunk.bucket - chunk.valid)))
        return (sl,)

    def gather(results, chunks, m):
        return np.concatenate([np.asarray(r[:, :c.valid])
                               for r, c in zip(results, chunks)], axis=1)

    dso = DynamicStreamOrchestrator(pool, pad_slice, gather)
    for m in (7, 16, 33, 70, 100):
        x = jnp.arange(m, dtype=jnp.float32)[None]
        out = dso.score((x,), m)
        np.testing.assert_allclose(out, np.asarray(x) * 2.0)
        assert out.shape == (1, m)
    dso.shutdown()


def test_concurrent_submissions():
    pool = _build_pool([16], n_streams=2)

    def pad_slice(request, chunk):
        x, = request
        sl = x[:, chunk.start:chunk.start + chunk.valid]
        if chunk.valid < chunk.bucket:
            sl = jnp.pad(sl, ((0, 0), (0, chunk.bucket - chunk.valid)))
        return (sl,)

    def gather(results, chunks, m):
        return np.concatenate([np.asarray(r[:, :c.valid])
                               for r, c in zip(results, chunks)], axis=1)

    dso = DynamicStreamOrchestrator(pool, pad_slice, gather, max_workers=8)
    xs = [jnp.full((1, 40), float(i)) for i in range(8)]
    futs = [dso.submit((x,), 40) for x in xs]
    for i, f in enumerate(futs):
        np.testing.assert_allclose(f.result(), np.full((1, 40), 2.0 * i))
    dso.shutdown()
