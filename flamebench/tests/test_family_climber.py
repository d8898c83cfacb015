"""The Climber family, loaded by path as a run loads it, gives bitwise the
values that the benchmark gave before it had model families: the weights
from a seed, the reference and control answers, the FLOP counts, the
kernel calls read from the recorded trace, the kernel's roofline share and
step MFU.  The frozen values were computed by the code the family replaced
(``weights.layout``, ``reference.py``, ``harness._ref_rows``,
``work.request_flops``, ``trace.kernel_call``, ``stats.roofline_share``),
on the same inputs."""
import gzip
import hashlib
import json
import os
import shutil

import jax
import numpy as np
import pytest

from flamebench import harness, stats, trace as TR, traffic as T
from flamebench import weights as W, work

DATA = os.path.join(os.path.dirname(__file__), "data")
CONFIGS = os.path.join(harness.ROOT, "flamebench", "configs")

OP = ("%_fused_kernel_call = bf16[1,4,32,128]{3,2,1,0:T(8,128)(2,1)S(1)} "
      "custom-call(s32[1,4]{1,0:T(1,128)S(1)} %bitcast.414, s32[4]{0} %a, "
      "f32[4,4]{1,0} %b, f32[4,4]{1,0} %c, bf16[1,4,32,128]{3,2,1,0} %q, "
      "s8[4,4,384,128]{3,2,1,0} %kh, s8[4,4,384,128]{3,2,1,0} %vh, "
      "bf16[1,4,32,128]{3,2,1,0} %kc, bf16[1,4,32,128]{3,2,1,0} %vc)")

#: request_flops(m, new / grown / cached) for m in (1, 8, 71, max_slate)
FLOPS = {
    "tiny": [13550592.0, 854016.0, 427008.0, 16539648.0, 3843072.0,
             3416064.0, 43441152.0, 30744576.0, 30317568.0, 26787840.0,
             14091264.0, 13664256.0],
    "climber-base": [10516193280.0, 88129536.0, 44064768.0, 10824646656.0,
                     396582912.0, 352518144.0, 13600727040.0, 3172663296.0,
                     3128598528.0, 16112418816.0, 5684355072.0,
                     5640290304.0],
    "climber-long": [22605225984.0, 100712448.0, 50356224.0, 22957719552.0,
                     453206016.0, 402849792.0, 26130161664.0, 3625648128.0,
                     3575291904.0, 48337256448.0, 25832742912.0,
                     25782386688.0],
}
#: roofline_share on the recorded trace: (with COUNTERS, without counters)
ROOFLINE = {"climber-base": (1.68164558537495, 0.9354380432298434),
            "climber-long": (2.358827389032382, 1.243961027135541)}
COUNTERS = {"dso_rows_dispatched": 300.0, "dso_dedup_rows_saved": 12.0,
            "dso_chunks_encode": 20.0, "dso_chunks_extend": 9.0,
            "dso_chunks_append": 0.0, "dso_dispatches_cached": 118.0,
            "dso_dispatches_decode": 0.0}


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _conf(name):
    if name == "tiny":
        return _json(DATA, "tiny.json")
    return _json(CONFIGS, f"{name}.json")


@pytest.fixture(scope="module")
def fam():
    return harness.family(_conf("tiny"), "tiny.json")


@pytest.fixture(scope="module")
def tiny(fam):
    conf = _conf("tiny")
    params = W.make_params(fam.layout(conf["model"]), 2**31 + 11)
    tr = T.Traffic(_json(DATA, "tiny_closed.json"),
                   n_history=conf["n_history"],
                   vocab=conf["model"]["vocab_size"], seed=2**31 + 13,
                   seconds=2.0)
    reqs = [tr.closed(i) for i in range(10)]      # a block and a part
    return conf, params, reqs


@pytest.fixture(scope="module")
def reduced(fam, tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "small.xplane.pb"
    with gzip.open(os.path.join(DATA, "trace_small.xplane.pb.gz")) as f, \
            open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    return TR.reduce(str(path), fam.KERNELS)


def _digest(arrays):
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a, np.float32)
        h.update(repr(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_weights_from_a_seed_are_the_parents(tiny):
    _, params, _ = tiny
    h = hashlib.sha256()
    for p, a in sorted(jax.tree_util.tree_leaves_with_path(params),
                       key=lambda x: jax.tree_util.keystr(x[0])):
        a = np.asarray(a)
        h.update(jax.tree_util.keystr(p).encode())
        h.update(str(a.dtype).encode())
        h.update(repr(a.shape).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == \
        "d2720486397b8722b7fec015c0dae10cfc0ea088663d09fe59a1db18fe2683c7"


@pytest.mark.parametrize("lowp,digest,first", [
    (False, "c6e57d3ceff4ef9fe65567ec38bd2386004746a63e14955f8826e3ba750a2d3e",
     0.5417171120643616),
    (True, "f27db387ac39a388a60c360a70391a037b57e1a9a35bb35f066b93e71b002f69",
     0.5411209464073181),
])
def test_reference_and_control_scores_are_the_parents(fam, tiny, lowp,
                                                      digest, first):
    conf, params, reqs = tiny
    rows = [fam.reference_row(r, conf["n_history"]) for r in reqs]
    got = harness._ref_rows(fam, params, conf["model"], rows,
                            int(conf["max_slate"]), lowp=lowp)
    assert [g.shape[0] for g in got] == [len(r.candidates) for r in reqs]
    assert float(got[0][0, 0]) == first
    assert _digest(got) == digest


def test_control_gap_is_the_parents(fam, tiny):
    conf, params, reqs = tiny
    gap = harness.score_gap(fam, params, conf["model"], conf["n_history"],
                            [{"req": r} for r in reqs],
                            int(conf["max_slate"]), lowp=True)
    assert gap == 0.040476441383361816


@pytest.mark.parametrize("name", sorted(FLOPS))
def test_request_flops_are_the_parents(fam, name):
    conf = _conf(name)
    got = [fam.request_flops(conf["model"], conf["n_history"], m,
                             new_user=nu, grew=g)
           for m in (1, 8, 71, conf["max_slate"])
           for nu, g in ((True, False), (False, True), (False, False))]
    assert got == FLOPS[name]


def test_kernel_calls_are_the_parents(fam, reduced):
    k = fam.KERNELS["fused_score"]
    assert k.op == "%_fused_kernel_call"
    assert k.parse(OP) == {"rows": 1, "heads": 4, "q_rows": 32,
                           "pool_rows": 4, "s_pad": 384, "kv_bytes": 1}
    calls = sorted((c["rows"], c["heads"], c["q_rows"], c["pool_rows"],
                    c["s_pad"], c["kv_bytes"], repr(c["seconds"]))
                   for c in reduced["kernels"])
    assert len(calls) == 144
    assert {c["kernel"] for c in reduced["kernels"]} == {"fused_score"}
    assert hashlib.sha256(repr(calls).encode()).hexdigest() == \
        "d22c3895a72ec873515abf77864ddf1469741aa13ffe6390239e6631ee24ac25"


@pytest.mark.parametrize("name", sorted(ROOFLINE))
def test_roofline_share_is_the_parents(fam, reduced, name):
    conf = _conf(name)
    rec = {"trace": reduced, "family": fam, "model": conf["model"],
           "n_history": conf["n_history"], "peaks": work.peaks("TPU v5 lite"),
           "trace_counters": COUNTERS}
    with_counters, without = ROOFLINE[name]
    assert stats.roofline_share(rec, "fused_score") == with_counters
    rec["trace_counters"] = {}
    assert stats.roofline_share(rec, "fused_score") == without
    assert stats.roofline_share(rec, "no_such_kernel") is None


def test_step_mfu_is_the_parents(fam, reduced):
    conf = _conf("climber-base")
    mix = T.load("session", os.path.join(harness.ROOT, "flamebench"))
    tr = T.Traffic(mix, n_history=512, vocab=conf["model"]["vocab_size"],
                   seed=2**31 + 17, seconds=51.0)
    reqs = [tr.closed(i) for i in range(200)]
    w0, w1 = 10.0, 10.0 + reduced["window_s"]
    log = [{"req": r, "m": len(r.candidates), "ok": i % 7 != 3,
            "done": w0 - 0.05 + i * (reduced["window_s"] + 0.1) / len(reqs)}
           for i, r in enumerate(reqs)]
    assert sum(1 for r in log if r["ok"] and w0 <= r["done"] <= w1) == 104
    rec = {"trace": reduced, "trace_window": (w0, w1), "requests": log,
           "family": fam, "model": conf["model"], "n_history": 512,
           "peaks": work.peaks("TPU v5 lite")}
    assert stats.step_mfu(rec) == 45.907677864518526
