"""The control — the reference in float8 in the program's place — comes
out not correct against each cell's committed limit, on three seeds, at
a test size."""
import json
import os

import pytest

from flamebench import control, harness

DATA = os.path.join(os.path.dirname(__file__), "data")


def _load(name):
    with open(os.path.join(DATA, f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell,mix", [
    ("climber-base.session", "tiny_closed"),
    ("climber-long.session", "tiny_session"),
    ("climber-base.session", "tiny_cold"),
])
def test_control_fails_the_cells_limit(cell, mix):
    limits = harness.load_json(harness.ROOT, "flamebench", "limits",
                               f"{cell}.json")
    for seed in (1, 2, 2**31 + 3):
        r = control.reading(cell, seed, conf=_load("tiny"), mix=_load(mix))
        assert r["value"] > limits[r["check"]], r
