"""A whole run at a test size on the CPU, the chip look skipped: sound, it
comes out correct; with one answer of every request altered where the
engine produces it, ``correct`` comes out false."""
import json
import os
import time

import numpy as np
import pytest

from flamebench import harness

DATA = os.path.join(os.path.dirname(__file__), "data")


def _load(name):
    with open(os.path.join(DATA, f"{name}.json")) as f:
        return json.load(f)


def _run(cell, mix, root):
    limits = harness.load_json(root, "flamebench", "limits", f"{cell}.json")
    return harness.run(cell, 2**31 + 5, 2.0, False,
                       t_start=time.perf_counter(), root=root,
                       conf=_load("tiny"), mix=_load(mix), limits=limits)


def _alter_scores(monkeypatch):
    from repro.serving.engine import FlameEngine

    gather = FlameEngine._gather

    def altered(self, rows, chunks, m, kind="full"):
        out = gather(self, rows, chunks, m, kind)
        if kind in ("cached", "full"):
            out = np.array(out, copy=True)
            out[0, 0, 0] += 0.25          # one answer of every request
        return out
    monkeypatch.setattr(FlameEngine, "_gather", altered)


@pytest.mark.parametrize("cell,mix", [
    ("climber-base.session", "tiny_closed"),
    ("climber-long.session", "tiny_session"),
    ("climber-base.session", "tiny_cold"),
])
def test_sound_run_is_correct_and_an_altered_answer_is_not(
        cell, mix, monkeypatch):
    sound = _run(cell, mix, harness.ROOT)
    assert sound["correct"], sound["checks"]
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert list(sound)[-1] == "checks"
    _alter_scores(monkeypatch)
    broken = _run(cell, mix, harness.ROOT)
    assert not broken["correct"], broken["checks"]
