"""The trace reduction, pinned on a small trace recorded on one TPU v5e
chip: 0.15 s of climber-base.session (run.py --trace 1 --trace-seconds
0.15)."""
import gzip
import math
import os
import shutil

import pytest

from flamebench import stats, trace as TR
from flamebench.families import climber

DATA = os.path.join(os.path.dirname(__file__), "data")

OP = ("%_fused_kernel_call = bf16[1,4,32,128]{3,2,1,0:T(8,128)(2,1)S(1)} "
      "custom-call(s32[1,4]{1,0:T(1,128)S(1)} %bitcast.414, s32[4]{0} %a, "
      "f32[4,4]{1,0} %b, f32[4,4]{1,0} %c, bf16[1,4,32,128]{3,2,1,0} %q, "
      "s8[4,4,384,128]{3,2,1,0} %kh, s8[4,4,384,128]{3,2,1,0} %vh, "
      "bf16[1,4,32,128]{3,2,1,0} %kc, bf16[1,4,32,128]{3,2,1,0} %vc)")


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "small.xplane.pb"
    with gzip.open(os.path.join(DATA, "trace_small.xplane.pb.gz")) as f, \
            open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    return TR.reduce(str(path), climber.KERNELS)


def test_kernel_call_shapes_from_hlo_text():
    assert climber.kernel_call(OP) == {"rows": 1, "heads": 4,
                                       "q_rows": 32, "pool_rows": 4,
                                       "s_pad": 384, "kv_bytes": 1}
    assert climber.kernel_call("%fusion.1 = bf16[4] fusion()") is None


def test_window_busy_and_kernel_time_are_pinned(reduced):
    assert math.isclose(reduced["window_s"], 0.150931628, rel_tol=1e-9)
    assert math.isclose(reduced["busy_s"], 0.008257727, rel_tol=1e-6)
    assert reduced["devices"] == 1
    ks = reduced["kernels"]
    assert len(ks) == 144
    assert math.isclose(sum(k["seconds"] for k in ks), 0.003705653,
                        rel_tol=1e-6)
    assert {(k["rows"], k["heads"], k["q_rows"], k["s_pad"], k["kv_bytes"])
            for k in ks} >= {(1, 4, 32, 384, 1)}


def test_breakdown_lists_top_ops_and_labelled_gaps(reduced):
    ops = reduced["device_ops"]
    assert len(ops) == 10 and ops[0][0] == "%_fused_kernel_call"
    assert all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))
    assert not any(n.startswith("%while") for n, _ in ops)
    gaps = reduced["idle_gaps"]
    assert len(gaps) == 10
    assert math.isclose(gaps[0][1], 0.005281076, rel_tol=1e-6)
    assert "DevicePut" in gaps[0][0]
    idle = 1 - reduced["busy_s"] / reduced["window_s"]
    assert 0.9 < idle < 0.96


def test_roofline_share_of_the_small_trace(reduced):
    rec = {"trace": reduced, "family": climber,
           "model": {"head_dim": 64, "climber": {"num_blocks": 2}},
           "n_history": 512,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "trace_counters": {"dso_rows_dispatched": 10.0,
                              "dso_dispatches_cached": 6.0}}
    share = stats.roofline_share(rec, "fused_score")
    assert 0.1 < share < 5.0
    # one distinct pool row per call is the least the calls can read
    rec["trace_counters"] = {}
    assert stats.roofline_share(rec, "fused_score") <= share
