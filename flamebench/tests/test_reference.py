"""The plain reference against the program's own float32 formulation at a
test size: scoring and a decode step after appended tokens agree to
float32 rounding, and the float8 control does not."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flamebench import weights as W
from flamebench.families import climber
from flamebench.families import climber_reference as reference

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def setup():
    from repro.models import build_model

    with open(os.path.join(DATA, "tiny.json")) as f:
        conf = json.load(f)
    model = conf["model"]
    bundle = build_model(climber.program_config(conf))
    params = W.make_params(climber.layout(model), 2**31 + 11)
    W.check_layout(params, jax.eval_shape(lambda k: bundle.init(k)[0],
                                          jax.random.key(0)))
    rng = np.random.default_rng(0)
    n = conf["n_history"]
    hist = rng.integers(0, model["vocab_size"], (2, n + 5)).astype(np.int32)
    cands = rng.integers(0, model["vocab_size"], (2, 12)).astype(np.int32)
    side = np.stack([reference.side_features(h) for h in hist])
    return conf, model, bundle, params, hist, cands, side


def _f32(params):
    return jax.tree.map(lambda a: a.astype(jnp.float32), params)


def test_scores_match_the_program_reference_in_float32(setup):
    conf, model, bundle, params, hist, cands, side = setup
    n = conf["n_history"]
    want = np.asarray(jax.jit(lambda p, b: bundle.prefill(
        p, b, impl="reference"))(_f32(params), {
            "history": hist[:, :n], "candidates": cands, "side": side}))
    got = reference.scores(params, model, hist[:, :n], side,
                           np.zeros((2, 1), np.int32), np.zeros(2, np.int32),
                           cands)
    assert np.abs(got - want).max() < 2e-5
    low = reference.scores(params, model, hist[:, :n], side,
                           np.zeros((2, 1), np.int32), np.zeros(2, np.int32),
                           cands, lowp=True)
    assert np.abs(low - want).max() > 1e-3


def test_decode_after_appended_tokens_matches_the_program(setup):
    conf, model, bundle, params, hist, cands, side = setup
    n = conf["n_history"]
    p32 = _f32(params)
    gen = np.array([[7, 9, 0], [11, 0, 0]], np.int32)
    glen = np.array([2, 1], np.int32)
    steps = 3

    @jax.jit
    def program(p, h, s, gen, glen, c):
        kv = bundle.encode_history(p, {"history": h, "side": s},
                                   impl="reference")
        pad = ((0, 0), (0, 0), (0, steps), (0, 0), (0, 0))
        kv = jax.tree.map(lambda a: jnp.pad(a, pad), kv)
        s0 = n // model["climber"]["num_blocks"] + 1
        for t in range(steps - 1):
            nxt = bundle.append_token(p, kv, gen[:, t:t + 1],
                                      jnp.full((2,), s0 + t, jnp.int32),
                                      impl="reference")
            keep = (t < glen)[:, None, None, None, None]
            kv = jax.tree.map(lambda a, b: jnp.where(keep, b, a), kv, nxt)
        return bundle.decode_logits(p, kv, c, s0 + glen, impl="reference")

    want = np.asarray(program(p32, hist[:, :n], side, gen, glen, cands))
    got = reference.scores(params, model, hist[:, :n], side, gen, glen,
                           cands)
    assert np.abs(got - want).max() < 2e-5


def test_side_features_are_the_mean_over_distinct_ids():
    h = np.array([3, 5, 3, 9], np.int32)
    want = np.mean([np.random.default_rng(i).standard_normal(
        12, dtype=np.float32) for i in (3, 5, 9)], axis=0)
    assert np.allclose(reference.side_features(h), want)
