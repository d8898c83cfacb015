"""The HSTU family, loaded by path as a run loads it: its layout is the
program's parameter tree, its FLOP counts and kernel work are the ones
worked out by hand here, its reference answers the same blocked and
unblocked (and the float8 control does not), and a whole run at a test
size on the CPU comes out correct, and not correct with an altered
answer."""
import json
import os
import time

import jax
import numpy as np
import pytest

from flamebench import harness, traffic as T, weights as W

DATA = os.path.join(os.path.dirname(__file__), "data")
CONF = os.path.join(harness.ROOT, "flamebench", "configs",
                    "hstu-large-4k.json")
CELL = "hstu-large-4k.session"

#: a call as the device trace names it: one packed row of 32 candidates,
#: 4 heads, over 4 int8 pool rows of 4,096 positions (a 128-lane pad)
OP = ("%_hstu_kernel_call.1 = bf16[1,4,32,128]{3,2,1,0:T(8,128)(2,1)S(1)} "
      "custom-call(s32[1,4]{1,0:T(1,128)S(1)} %bitcast.23, "
      "f32[4,4]{1,0} %bitcast.21, f32[4,4]{1,0} %bitcast.22, "
      "f32[1]{0} %bitcast.17, bf16[1,4,32,128]{3,2,1,0} %pad.7, "
      "s8[4,4,4096,128]{3,2,1,0} %pad.8, s8[4,4,4096,128]{3,2,1,0} %pad.9, "
      "f32[4,1,4096]{2,1,0} %copy-done.6, bf16[1,4,32,128]{3,2,1,0} "
      "%pad.10, bf16[1,4,32,128]{3,2,1,0} %pad.11)")


def _json(path):
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def fam():
    return harness.family(_json(CONF), CONF)


@pytest.fixture(scope="module")
def tiny(fam):
    from repro.models import build_model

    conf = _json(os.path.join(DATA, "tiny_hstu.json"))
    bundle = build_model(fam.program_config(conf))
    params = W.make_params(fam.layout(conf["model"]), 2**31 + 23)
    return conf, bundle, params


def test_layout_is_the_programs_tree(fam, tiny):
    from repro.models import build_model

    conf, bundle, params = tiny
    W.check_layout(params, jax.eval_shape(lambda k: bundle.init(k)[0],
                                          jax.random.key(0)))
    big = _json(CONF)
    program = build_model(fam.program_config(big))
    W.check_layout(
        jax.eval_shape(lambda: W.make_params(fam.layout(big["model"]), 1)),
        jax.eval_shape(lambda k: program.init(k)[0], jax.random.key(0)))


def test_config_is_hstu_large_at_a_4096_window():
    conf = _json(CONF)
    m = conf["model"]
    assert (m["d_model"], m["n_layers"], m["n_heads"], m["head_dim"]) == \
        (256, 8, 4, 64)
    assert m["hstu"] == {"max_seq_len": 4096, "num_time_buckets": 128,
                         "num_tasks": 3}
    assert conf["n_history"] == 4096
    entry = next(c for c in _json(os.path.join(
        harness.ROOT, "BENCHMARK.json"))["configs"]
        if c["name"] == "hstu-large-4k")
    assert entry["reduced"] == []


def test_request_flops_by_hand(fam):
    model = _json(CONF)["model"]
    n, m = 4096, 256
    # per token per layer: f1 256 x 1024 and f2 256 x 256, 2 FLOPs a MAC
    proj = 2 * (256 * 1024 + 256 * 256)
    # a miss: 4,096 tokens' projections and QK + AV over the causal pairs
    encode = 8 * (n * proj + 2 * 2 * 256 * n * (n + 1) // 2)
    assert encode == 90_211_090_432
    # a hit: 256 candidates over the 4,096 pooled positions and themselves
    cached = 8 * m * (proj + 2 * 2 * 256 * (n + 1))
    assert cached == 9_934_209_024
    assert fam.request_flops(model, n, m, new_user=True, grew=False) == \
        encode + cached
    # growth past the window re-encodes nothing: an extend costs a hit
    assert fam.request_flops(model, n, m, new_user=False, grew=True) == \
        cached
    assert fam.request_flops(model, n, m, new_user=False, grew=False) == \
        cached


def test_flops_are_the_programs_analytic_count(fam):
    """The family's counts, kept apart from the program, equal the
    program's own (``core/sumi.py``) for a miss and a hit."""
    from repro.core import sumi

    model = _json(CONF)["model"]
    for n, m in ((4096, 256), (24, 7), (512, 1)):
        whole = sumi.pointwise_flops_per_request(n, m, 8, 256, 256)
        assert fam.request_flops(model, n, m, new_user=True,
                                 grew=False) == whole
        assert fam.request_flops(model, n, m, new_user=False, grew=False) \
            == whole - sumi.pointwise_flops_per_request(n, 0, 8, 256, 256)


def test_kernel_parser_and_least_work(fam):
    k = fam.KERNELS["hstu_score"]
    assert k.op == "%_hstu_kernel_call"
    call = k.parse(OP)
    assert call == {"rows": 1, "heads": 4, "q_rows": 32, "pool_rows": 4,
                    "s_pad": 4096, "kv_bytes": 1}
    assert k.parse("%_hstu_kernel_call = bf16[1,4,32,128] custom-call()") \
        is None
    model = _json(CONF)["model"]
    # 10 rows over 5 dispatches, 2 of them encodes: 8 stacked KV rows in
    # 4 cached dispatches, so 2 distinct pool rows a call
    counters = {"dso_rows_dispatched": 10.0, "dso_dedup_rows_saved": 0.0,
                "dso_chunks_encode": 2.0, "dso_chunks_extend": 0.0,
                "dso_dispatches_cached": 4.0}
    flops, nbytes = k.work(call, model, 4096, counters)
    q = 1 * 32 * 4 * 64                       # query elements at D = 64
    assert flops == 2 * 2 * q * (4096 + 1)
    # 2 pool rows: K and V at 1 byte, the bias at 4 bytes a position, the
    # scales; queries, candidate K/V and outputs in bf16
    assert nbytes == 2 * 4096 * (4 * 64 * 2 + 4) + 4 * q * 2 + 2 * 4 * 8


def test_reference_blocked_and_unblocked_agree(fam, tiny):
    conf, _, params = tiny
    model, n = conf["model"], conf["n_history"]
    mix = _json(os.path.join(DATA, "tiny_closed.json"))
    tr = T.Traffic(mix, n_history=n, vocab=model["vocab_size"],
                   seed=2**31 + 3, seconds=2.0)
    ref = fam.reference
    for r in [tr.closed(i) for i in range(3)]:
        ids, times, cands = fam.reference_row(r, n)
        np.testing.assert_array_equal(times, ref.action_times(r.user_id,
                                                              ids))
        blocked = ref.scores(params, model, ids, times, cands, block=8)
        whole = ref.scores(params, model, ids, times, cands, block=None)
        np.testing.assert_allclose(blocked, whole, atol=1e-6, rtol=0)
        low = ref.scores(params, model, ids, times, cands, lowp=True)
        assert np.abs(low - whole).max() > 1e-3


def _run(fam_conf, mix):
    # the test size's own limit: int8 K/V over 24 positions of 16-wide
    # heads read about 0.02 (tests/test_hstu.py); an altered answer 0.25
    return harness.run(CELL, 2**31 + 5, 2.0, False,
                       t_start=time.perf_counter(), conf=fam_conf,
                       mix=mix, limits={"score_gap": 0.05})


def test_whole_run_at_test_size_is_correct_and_not_when_altered(
        monkeypatch):
    conf = _json(os.path.join(DATA, "tiny_hstu.json"))
    mix = _json(os.path.join(DATA, "tiny_closed.json"))
    sound = _run(conf, mix)
    assert sound["correct"], sound["checks"]
    assert sound["attempted"] > 0 and sound["failed"] == 0

    from repro.serving.engine import FlameEngine
    gather = FlameEngine._gather

    def altered(self, rows, chunks, m, kind="full"):
        out = gather(self, rows, chunks, m, kind)
        if kind == "cached":
            out = np.array(out, copy=True)
            out[0, 0, 0] += 0.25
        return out
    monkeypatch.setattr(FlameEngine, "_gather", altered)
    broken = _run(conf, mix)
    assert not broken["correct"], broken["checks"]
