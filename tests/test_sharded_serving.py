"""Mesh-sharded serving: rule resolution, per-shard pool budgets, and
end-to-end executor parity on a forced multi-device host mesh.

Layers of coverage:

  1. rule/spec unit tests — ``resolve_rules`` axis dropping,
     ``serving_rules``'s replicated ``cache_batch`` + CP fallback,
     ``logical_to_spec``'s per-dim divisibility fallback / used-axis dedup
     / trailing-``None`` trim, ``rules_for_shape``'s batch-ways flip, and
     the ``SERVING_KV_LEAF`` layout all executors and the pool share.
     These run against ``AbstractMesh`` so the main pytest process keeps
     its single device;
  2. ``CoalescePolicy`` mesh scaling — ``max_batch`` / ``pack_rows`` are
     per-device capacities, the compiled global axes scale by
     ``data_ways`` (which is also what keeps the per-device local shape —
     and hence XLA's kernel choice and FP reduction order — identical to
     a single-device engine);
  3. ``make_serving_mesh`` CLI resolution;
  4. subprocess (4 forced host devices) — data-parallel (4,1) serving is
     BITWISE identical to the single-device engine for reference and
     chunked impls over an int8 pool; a (2,2) tensor-parallel mesh agrees
     to f32-reassociation tolerance, halves the per-shard pool bytes, and
     no executor's compiled HLO contains a cross-shard reshard collective
     (all-to-all / collective-permute) on the steady-state hot path;
  5. subprocess (4 forced host devices) — the per-row executor signature
     under a mesh, served concurrently: encode/extend executors return
     their rows split in the pool's head-sharded layout, misses, hits and
     extends racing pool publishes against dispatches finish (no
     deadlock), and scores match the single-device engine.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro import sharding as shd
from repro.core.dso import CoalescePolicy
from repro.launch.mesh import make_serving_mesh


def _amesh(shape, axes):
    return AbstractMesh(tuple(shape), tuple(axes))


# ---------------------------------------------------------------------------
# 1. rule / spec resolution
# ---------------------------------------------------------------------------

def test_resolve_rules_drops_missing_axes():
    mesh = _amesh((2, 2), ("data", "model"))
    rules = shd.resolve_rules(mesh)
    # 'pod' exists in DEFAULT_RULES targets but not on this mesh
    assert rules["batch"] == ("data",)
    assert rules["cache_batch"] == ("data",)
    assert rules["experts"] == ("data",)
    for axes in rules.values():
        assert all(a in ("data", "model") for a in axes)
    # a mesh WITH a pod axis keeps it, in rule order
    mesh3 = _amesh((2, 2, 2), ("pod", "data", "model"))
    assert shd.resolve_rules(mesh3)["batch"] == ("pod", "data")


def test_serving_rules_replicated_cache_batch_and_cp_fallback():
    mesh = _amesh((2, 2), ("data", "model"))
    # TP case: heads divide the model ways -> history length unsharded
    rules = shd.serving_rules(mesh, kv_heads=4)
    assert rules["batch"] == ("data",)
    assert rules["cache_batch"] == ()          # reshard-free dedup gather
    assert rules["cache_heads"] == ("model",)
    assert rules["cache_seq_shard"] == ()
    # CP fallback: 3 heads on a 2-way model axis cannot head-shard
    rules = shd.serving_rules(mesh, kv_heads=3)
    assert rules["cache_seq_shard"] == ("model",)
    # no model axis at all -> no fallback either
    rules = shd.serving_rules(_amesh((4,), ("data",)), kv_heads=3)
    assert rules["cache_seq_shard"] == ()
    assert rules["cache_heads"] == ()
    # unknown head count: stay on the TP layout
    assert shd.serving_rules(mesh)["cache_seq_shard"] == ()


def test_logical_to_spec_divisibility_fallback():
    mesh = _amesh((2, 2), ("data", "model"))
    rules = shd.serving_rules(mesh, kv_heads=4)
    # [U, L, S, Hkv, D] with Hkv divisible -> heads take the model axis
    spec = shd.logical_to_spec(shd.SERVING_KV_LEAF, (3, 2, 33, 4, 16),
                               mesh, rules)
    assert spec == P(None, None, None, "model")
    # Hkv NOT divisible by the model ways -> dropped (replicated), and the
    # trailing-None trim leaves an empty spec
    spec = shd.logical_to_spec(shd.SERVING_KV_LEAF, (3, 2, 33, 3, 16),
                               mesh, rules)
    assert spec == P()
    # int8 scale leaf [U, L, 1, Hkv, 1] under the CP-fallback rules: the
    # size-1 sequence dim cannot take the model axis
    cp = shd.serving_rules(mesh, kv_heads=3)
    assert shd.logical_to_spec(shd.SERVING_KV_LEAF, (3, 2, 1, 3, 1),
                               mesh, cp) == P()
    # ... while the value leaf's even history length can
    assert shd.logical_to_spec(shd.SERVING_KV_LEAF, (3, 2, 64, 3, 16),
                               mesh, cp) == P(None, None, "model")


def test_logical_to_spec_used_axis_dedup_and_compose():
    mesh = _amesh((2, 2), ("data", "model"))
    # one mesh axis is spent on the first logical dim that claims it
    spec = shd.logical_to_spec(("batch", "seq_shard"), (4, 8), mesh)
    assert spec == P("data")
    # multi-axis compose: a rule listing two axes takes both when both
    # divide, as a tuple entry
    rules = dict(shd.resolve_rules(mesh))
    rules["tokens"] = ("data", "model")
    assert shd.logical_to_spec(("tokens",), (8,), mesh, rules) \
        == P(("data", "model"))
    # ... and only the dividing prefix when the dim is odd after one split
    assert shd.logical_to_spec(("tokens",), (6,), mesh, rules) == P("data")


def test_rules_for_shape_batch_ways_flip():
    mesh = _amesh((2, 2), ("data", "model"))
    # plenty of batch: default rules, fsdp shards embed over data
    rules = shd.rules_for_shape(mesh, global_batch=8)
    assert rules["cache_seq"] == () and rules["seq"] == ()
    assert rules["embed"] == ("data",)
    # batch-1 workload: the unshardable batch axis hands data (and model)
    # to the sequence axes instead
    rules = shd.rules_for_shape(mesh, global_batch=1)
    assert rules["cache_seq"] == ("data", "model")
    assert rules["seq"] == ("data",)
    assert shd.rules_for_shape(mesh, global_batch=8, fsdp=False)["embed"] \
        == ()


# ---------------------------------------------------------------------------
# 2. mesh-aware coalescing capacity
# ---------------------------------------------------------------------------

def test_coalesce_policy_scales_per_device_capacity():
    pol = CoalescePolicy(max_batch=4, data_ways=4)
    assert pol.batch == 16 and pol.rows == 16
    pol = CoalescePolicy(max_batch=4, pack_rows=2, data_ways=4)
    assert pol.batch == 16 and pol.rows == 8
    # no mesh: unchanged single-device semantics
    pol = CoalescePolicy(max_batch=4)
    assert pol.batch == 4 and pol.rows == 4
    assert CoalescePolicy(enabled=False, max_batch=4, data_ways=4).batch == 1
    with pytest.raises(ValueError):
        CoalescePolicy(data_ways=0)


# ---------------------------------------------------------------------------
# 3. CLI mesh resolution
# ---------------------------------------------------------------------------

def test_make_serving_mesh():
    assert make_serving_mesh("", 0) is None
    mesh = make_serving_mesh("1,1")
    assert mesh.axis_names == ("data", "model")
    assert dict(mesh.shape) == {"data": 1, "model": 1}
    assert make_serving_mesh(model_parallel=1).shape["model"] == 1
    with pytest.raises(ValueError):
        make_serving_mesh("4")
    with pytest.raises(ValueError):
        make_serving_mesh("2,0")


# ---------------------------------------------------------------------------
# 4. forced multi-device end-to-end parity
# ---------------------------------------------------------------------------

SUBPROCESS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, "src")
import dataclasses, numpy as np, jax
from repro.configs import get_config
from repro.models import build_model
from repro.types import ClimberConfig
from repro.launch.mesh import make_serving_mesh
from repro.serving import create_engine

cfg = dataclasses.replace(get_config("climber"), vocab_size=5000, d_model=64,
                          d_ff=256, n_heads=4, n_kv_heads=4, head_dim=16,
                          climber=ClimberConfig(num_blocks=2,
                                                layers_per_block=2))
bundle = build_model(cfg)
params, _ = bundle.init(jax.random.key(0))


def run(mesh, impl, dtype):
    eng = create_engine("flame", bundle, params, n_history=64, buckets=(16,),
                        history_cache=True, pool_slots=16, pool_dtype=dtype,
                        impl=impl, mesh=mesh)
    rr = np.random.default_rng(0)
    res = []
    for i in range(6):
        h = rr.integers(0, 5000, 64).astype(np.int32)
        c = rr.integers(0, 5000, 11).astype(np.int32)
        res.append(np.asarray(eng.serve(h, c, user_id=i % 2)))
    gauges = {k: v for k, v in eng.metrics().items()
              if "shard" in k or k == "pool_bytes_used"}
    hlo = {kb: ex.as_text() for kb, ex in eng.dso.compiled.items()}
    eng.shutdown()
    return np.concatenate([r.ravel() for r in res]), gauges, hlo


RESHARD = ("all-to-all", "collective-permute")
# encode/extend may all-gather their OUTPUT: that is the one-time publish
# of fresh KV into the pool's replicated cache_batch layout.  The
# steady-state scoring kinds (cached/full) must stay reshard-free.
PUBLISH_KINDS = ("encode", "extend")

# data-parallel (4,1): bitwise vs single-device, scoring collective-free
for impl in ("reference", "chunked"):
    base, _, _ = run(None, impl, "int8")
    out, g, hlo = run(make_serving_mesh("4,1"), impl, "int8")
    assert np.array_equal(base, out), (impl, float(np.abs(base - out).max()))
    assert g.get("pool_shard_ways") == 1, g
    assert g.get("pool_bytes_shard0", 0) > 0, g
    assert g.get("pool_bytes_used_shard0", 0) == g["pool_bytes_shard0"], g
    for (kind, b), txt in hlo.items():
        ops = RESHARD if kind in PUBLISH_KINDS \
            else RESHARD + ("all-reduce", "all-gather")
        for op in ops:
            assert op not in txt, (impl, kind, b, op)

# tensor+data (2,2): f32-reassociation tolerance (the head-sharded
# out-projection all-reduces partial sums — reassociation, not a reshard
# — and the per-layer ~1e-7 drift compounds through the block stack),
# per-shard pool bytes halve, still no reshard collectives
base, _, _ = run(None, "chunked", "native")
out, g, hlo = run(make_serving_mesh("2,2"), "chunked", "native")
assert np.allclose(base, out, atol=5e-3), float(np.abs(base - out).max())
assert g.get("pool_shard_ways") == 2, g
assert g["pool_bytes_shard0"] == g["pool_bytes_shard1"] > 0, g
# every stored byte sits on exactly one model shard
assert g["pool_bytes_used_shard0"] == g["pool_bytes_used_shard1"] > 0, g
assert 2 * g["pool_bytes_used_shard0"] == g["pool_bytes_used"], g
for kb, txt in hlo.items():
    for op in RESHARD:
        assert op not in txt, (kb, op)
print("OK")
"""


def test_sharded_serving_multi_device_subprocess():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", SUBPROCESS_SCRIPT],
                         cwd=os.path.join(os.path.dirname(__file__), ".."),
                         env=env, capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "OK" in res.stdout


CONCURRENT_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, "src")
import dataclasses, numpy as np, jax
from repro.configs import get_config
from repro.models import build_model
from repro.types import ClimberConfig
from repro.launch.mesh import make_serving_mesh
from repro.serving import create_engine
from repro.serving.scheduler import run_workload_async

cfg = dataclasses.replace(get_config("climber"), vocab_size=5000, d_model=64,
                          d_ff=256, n_heads=4, n_kv_heads=4, head_dim=16,
                          climber=ClimberConfig(num_blocks=2,
                                                layers_per_block=2))
bundle = build_model(cfg)
params, _ = bundle.init(jax.random.key(0))
rr = np.random.default_rng(3)
hists = [rr.integers(0, 5000, 72).astype(np.int32) for _ in range(8)]
grown = [np.append(h, rr.integers(0, 5000, 2).astype(np.int32))
         for h in hists]
waves = [[{"history": h, "user_id": u,
           "candidates": rr.integers(0, 5000, int(rr.integers(5, 30)))
           .astype(np.int32)} for u, h in enumerate(hs)]
         for hs in (hists, hists, grown)]   # misses, hits, extends


def serve(mesh):
    eng = create_engine("flame", bundle, params, n_history=64,
                        buckets=(16,), history_cache=True, pool_slots=32,
                        pool_dtype="int8", impl="chunked",
                        incremental_history=True, mesh=mesh)
    outs = []
    for wave in waves:
        res = run_workload_async(eng, wave, result_timeout_s=240)
        assert res["resolved"] == len(wave), res
        outs += [np.asarray(o, np.float32).ravel() for o in res["outputs"]]
    return eng, np.concatenate(outs)


base_eng, base = serve(None)
base_eng.shutdown()
eng, out = serve(make_serving_mesh("2,2"))
m = eng.metrics()
assert m["pool_hits"] > 0 and m["pool_misses"] > 0, m
assert m["pool_extensions"] > 0, m
assert m["dso_ingraph_dispatches"] == \
    m["dso_dispatches"] - m["dso_dispatches_encode"], m
B = eng.dso.policy.batch
for kind in ("encode", "extend"):
    exes = [ex for (k, _), ex in eng.dso.compiled.items() if k == kind]
    assert exes, kind
    for ex in exes:
        rows, shardings = ex.out_info, ex.output_shardings
        assert isinstance(rows, tuple) and len(rows) == B, (kind, rows)
        for info, sh in zip(jax.tree.leaves(rows),
                            jax.tree.leaves(shardings)):
            assert info.shape[0] == 1, (kind, info.shape)
            assert sh == eng._arg_sharding(info.shape), (kind, sh)
        # 5-d KV leaves split over the model axis, as the pool stores them
        assert any(len(sh.spec) > 3 and sh.spec[3] == "model"
                   for sh in jax.tree.leaves(shardings)), kind
eng.shutdown()
assert np.allclose(base, out, atol=5e-3), float(np.abs(base - out).max())
print("OK")
"""


def test_concurrent_mesh_serving_per_row_outputs_subprocess():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", CONCURRENT_MESH_SCRIPT],
                         cwd=os.path.join(os.path.dirname(__file__), ".."),
                         env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "OK" in res.stdout


def test_single_device_mesh_engine_matches_no_mesh(climber_engine_pair):
    """A (1,1) mesh engine must be bitwise identical to a mesh-less one in
    the SAME process — the sharding plumbing (SDS in-shardings, eval_shape
    out-shardings, mesh_rules trace context) is a no-op at 1 way."""
    eng_plain, eng_mesh = climber_engine_pair
    rr = np.random.default_rng(7)
    for i in range(4):
        h = rr.integers(0, 5000, 64).astype(np.int32)
        c = rr.integers(0, 5000, 9).astype(np.int32)
        a = np.asarray(eng_plain.serve(h, c, user_id=i % 2))
        b = np.asarray(eng_mesh.serve(h, c, user_id=i % 2))
        np.testing.assert_array_equal(a, b)
    # mesh engine surfaces per-shard pool accounting even at 1 way
    m = eng_mesh.metrics()
    assert m.get("pool_shard_ways") == 1
    assert m.get("pool_bytes_shard0", 0) > 0


@pytest.fixture(scope="module")
def climber_engine_pair():
    import dataclasses

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import create_engine
    from repro.types import ClimberConfig

    cfg = dataclasses.replace(
        get_config("climber"), vocab_size=5000, d_model=64, d_ff=128,
        n_heads=2, n_kv_heads=2, head_dim=16,
        climber=ClimberConfig(num_blocks=2, layers_per_block=2))
    bundle = build_model(cfg)
    params, _ = bundle.init(jax.random.key(0))
    kw = dict(n_history=64, buckets=(16,), history_cache=True,
              pool_slots=16, pool_dtype="int8", impl="chunked")
    eng_plain = create_engine("flame", bundle, params, **kw)
    eng_mesh = create_engine("flame", bundle, params,
                             mesh=make_serving_mesh("1,1"), **kw)
    yield eng_plain, eng_mesh
    eng_plain.shutdown()
    eng_mesh.shutdown()
