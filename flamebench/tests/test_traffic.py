"""The traffic generator: deterministic per seed, the same work on every
seed, in another order."""
import json
import os

import numpy as np
import pytest

from flamebench import traffic as T

DATA = os.path.join(os.path.dirname(__file__), "data")


def _mix(name):
    with open(os.path.join(DATA, f"{name}.json")) as f:
        return json.load(f)


def _traffic(mix, seed, seconds=4.0):
    return T.Traffic(mix, n_history=64, vocab=5000, seed=seed,
                     seconds=seconds)


def _sig(reqs):
    return [(r.user_id, r.history.tobytes(), r.candidates.tobytes(),
             r.new_user, r.grew, round(r.due, 9)) for r in reqs]


def test_same_seed_same_requests():
    mix = _mix("tiny_session")
    a, b = _traffic(mix, 2**31 + 7), _traffic(mix, 2**31 + 7)
    assert _sig(a.warm) == _sig(b.warm)
    assert _sig(a.window) == _sig(b.window)


def test_other_seed_same_work_other_order():
    mix = _mix("tiny_session")
    a, b = _traffic(mix, 1), _traffic(mix, 2)
    assert _sig(a.window) != _sig(b.window)
    n_w = round(mix["rate_per_s"] * 4.0)
    for pick in (lambda r: len(r.candidates), lambda r: r.new_user,
                 lambda r: r.grew):
        assert sorted(map(pick, a.window)) == sorted(map(pick, b.window))
        assert sorted(map(pick, a.window[:n_w])) == \
            sorted(map(pick, b.window[:n_w]))

    def window_gaps(t):
        return sorted(np.round(np.diff([r.due for r in t.window[:n_w]]
                                       + [4.0]), 9))
    assert window_gaps(a) == window_gaps(b)


def test_open_loop_schedule_covers_window_and_drain():
    mix = _mix("tiny_session")
    t = _traffic(mix, 3, seconds=4.0)
    rate = mix["rate_per_s"]
    due = [r.due for r in t.window]
    assert len(due) == round(rate * 4.0) + round(rate * mix["drain_s"])
    assert due == sorted(due) and due[0] == 0.0
    # exactly rate x seconds arrivals fall in the window, the rest after it
    assert sum(d < 4.0 for d in due) == round(rate * 4.0)
    assert due[round(rate * 4.0)] == 4.0
    assert due[-1] < 4.0 + mix["drain_s"]


def test_open_loop_window_mixes_new_users_and_grown_histories():
    mix = _mix("tiny_session")
    t = _traffic(mix, 4)
    assert len(t.warm) == mix["warm"]
    assert any(r.new_user for r in t.window)
    assert any(not r.new_user for r in t.window)
    grew = [r for r in t.window if r.grew]
    assert grew and all(len(r.history) > 64 + mix["history_extra"]
                        for r in grew)


def test_slates_follow_the_clipped_lognormal():
    mix = _mix("tiny_session")
    m = T.slate_sizes(mix, 4096)
    s = mix["slate"]
    assert m.min() >= s["min"] and m.max() <= s["max"]
    assert np.median(m) == s["median"]


def _closed(t, n):
    return [t.closed(i) for i in range(n)]


def test_closed_loop_by_index_is_deterministic():
    mix = _mix("tiny_closed")
    a, b = _traffic(mix, 2**31 + 9), _traffic(mix, 2**31 + 9)
    assert _sig(a.warm) == _sig(b.warm)
    assert _sig(_closed(a, 300)) == _sig(_closed(b, 300))


@pytest.mark.parametrize("n", [T.BLOCK, 3 * T.BLOCK, 5 * T.BLOCK])
def test_closed_loop_sends_the_same_work_on_every_seed(n):
    mix = _mix("tiny_closed")
    a, b = _traffic(mix, 1), _traffic(mix, 2**31 + 2)
    ra, rb = _closed(a, n), _closed(b, n)
    assert [r.user_id for r in ra] != [r.user_id for r in rb]
    for pick in (lambda r: len(r.candidates), lambda r: r.new_user,
                 lambda r: r.grew):
        assert sorted(map(pick, a.warm)) == sorted(map(pick, b.warm))
        assert sorted(map(pick, ra)) == sorted(map(pick, rb))


def test_closed_loop_warm_is_the_start_of_its_sequence_and_users_grow():
    mix = _mix("tiny_closed")
    t = _traffic(mix, 7)
    assert len(t.warm) == mix["warm"]
    win = _closed(t, 4 * T.BLOCK)
    seen = {r.user_id for r in t.warm}
    for r in win:
        assert r.new_user == (r.user_id not in seen)
        assert not (r.new_user and r.grew)
        seen.add(r.user_id)
    assert any(r.new_user for r in win)
    grew = [r for r in win if r.grew]
    assert grew and all(len(r.history) > 64 + mix["history_extra"]
                        for r in grew)
    # grow_share of each block's requests grow, where it has repeat visits
    assert len(grew) <= round(mix["grow_share"] * T.BLOCK) * 4


def test_cold_requests_are_all_new_users():
    mix = _mix("tiny_cold")
    t = _traffic(mix, 5)
    reqs = t.warm + [t.closed(i) for i in range(50)]
    assert all(r.new_user for r in reqs)
    assert len({r.user_id for r in reqs}) == len(reqs)
