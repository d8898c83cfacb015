"""The yardstick's FLOP and byte counts (Climber's family) and the peaks
table."""
import pytest

from flamebench import work
from flamebench.families import climber

BASE = dict(n_history=512, n_blocks=2, layers_per_block=12, d_model=256,
            d_ff=1024)
LONG = dict(BASE, n_history=1024)
MODEL = {"d_model": 256, "d_ff": 1024, "head_dim": 64,
         "climber": {"num_blocks": 2, "layers_per_block": 12}}


@pytest.mark.parametrize("shape,m", [(BASE, 128), (BASE, 71), (LONG, 512),
                                     (LONG, 285)])
def test_flop_copies_match_the_program(shape, m):
    from repro.core import sumi

    args = (shape["n_history"], m, shape["n_blocks"],
            shape["layers_per_block"], shape["d_model"], shape["d_ff"])
    assert climber.flops_per_request(*args) == sumi.flops_per_request(*args)
    assert climber.cached_flops_per_request(*args) == \
        sumi.cached_flops_per_request(*args)


def test_request_flops_adds_encode_only_for_new_users():
    def count(**kw):
        return climber.request_flops(MODEL, 512, 128, **kw)
    hit = count(new_user=False, grew=False)
    miss = count(new_user=True, grew=False)
    grew = count(new_user=False, grew=True)
    assert hit == climber.cached_flops_per_request(512, 128, 2, 12, 256,
                                                   1024)
    assert miss - hit == climber.flops_per_request(512, 0, 2, 12, 256, 1024)
    assert 0 < grew - hit < (miss - hit) / 100   # one side token per block


def test_kernel_work_counts_logical_head_dim_64():
    flops, nbytes = climber.kernel_work(rows=1, q_rows=32, heads=4,
                                        head_dim=64, s_hist=257,
                                        unique_rows=1, kv_bytes=1)
    assert flops == 2 * 2 * 32 * 4 * 64 * 258
    # int8 K and V of one pool row, bf16 q/k/v/out of the 32 rows, scales
    assert nbytes == 257 * 4 * 64 * 2 + 4 * 32 * 4 * 64 * 2 + 4 * 8
    padded, _ = climber.kernel_work(rows=1, q_rows=32, heads=4,
                                    head_dim=128, s_hist=257, unique_rows=1,
                                    kv_bytes=1)
    assert padded == 2 * flops


def test_peaks_known_device_and_unknown_raises():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
