"""End-to-end behaviour of the FLAME system (paper pipeline composed)."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import GRInteractionDataset, make_batch_iterator
from repro.models import build_model
from repro.serving import FlameEngine
from repro.serving.scheduler import TrafficConfig, generate_traffic, run_workload
from repro.training.loop import train
from repro.training.optimizer import AdamWConfig
from repro.types import ClimberConfig


@pytest.fixture(scope="module")
def trained_climber():
    cfg = dataclasses.replace(
        get_config("climber"), vocab_size=5_000, d_model=64, d_ff=128,
        n_heads=2, n_kv_heads=2, head_dim=32,
        climber=ClimberConfig(num_blocks=2, layers_per_block=2))
    bundle = build_model(cfg)
    ds = GRInteractionDataset(n_items=5_000, n_users=500, seed=0)
    it = make_batch_iterator(ds, 16, n_history=32, n_candidates=8)
    params, _, hist = train(bundle, it, 30, AdamWConfig(lr=3e-3,
                                                        warmup_steps=5),
                            log_every=30, impl="reference")
    return cfg, bundle, params, ds, hist


def test_train_then_serve_pipeline(trained_climber):
    """Train Climber on synthetic interactions, then serve it through the
    full PDA->DSO->FKE pipeline under mixed traffic."""
    cfg, bundle, params, ds, hist = trained_climber
    assert hist[-1]["loss"] < hist[0]["loss"]

    eng = FlameEngine(bundle, params, n_history=32, buckets=(32, 16, 8),
                      n_streams=2)
    tc = TrafficConfig(n_requests=12, n_history=32,
                       candidate_counts=(8, 16, 24), distribution="jittered",
                       seed=1)
    reqs = generate_traffic(tc, n_items=5_000)
    res = run_workload(lambda h, c: eng.serve(h, c), reqs, concurrency=3)
    assert res["requests"] == 12
    assert res["throughput_items_per_s"] > 0
    summary = eng.metrics()
    assert summary["requests"] == 12
    assert summary["p99_latency_ms"] >= summary["mean_latency_ms"] * 0.5
    eng.shutdown()


def test_served_scores_track_planted_preferences(trained_climber):
    """After training, candidates the generator marks positive should score
    higher on average than negatives — the system serves *useful* results."""
    cfg, bundle, params, ds, _ = trained_climber
    rng = np.random.default_rng(7)
    pos, neg = [], []
    for _ in range(40):
        r = ds.sample_request(rng, 32, 8)
        batch = {k: jnp.asarray(v)[None] for k, v in r.items()
                 if k in ("history", "candidates", "side")}
        scores = np.asarray(bundle.prefill(params, batch))[0]   # [M,T]
        lab = r["labels"]
        pos.extend(scores[lab[:, 0] > 0.5, 0].tolist())
        neg.extend(scores[lab[:, 0] < 0.5, 0].tolist())
    assert np.mean(pos) > np.mean(neg)


def test_dryrun_machinery_importable():
    """dryrun helpers are unit-testable without 512 devices: importing the
    module sets no XLA flags (only running it as __main__ does)."""
    flags = os.environ.get("XLA_FLAGS")
    from repro.launch.dryrun import _with_layers, should_skip
    assert os.environ.get("XLA_FLAGS") == flags
    from repro.configs import get_shape
    cfg = get_config("qwen2-72b")
    assert should_skip(cfg, get_shape("long_500k")) is not None
    assert should_skip(cfg, get_shape("train_4k")) is None
    assert should_skip(get_config("rwkv6-7b"), get_shape("long_500k")) is None
    c1 = _with_layers(cfg, 1)
    assert c1.n_layers == 1
    cg = _with_layers(get_config("gemma3-12b"), 2)
    assert cg.n_layers == 12      # 2 x period-6 pattern
