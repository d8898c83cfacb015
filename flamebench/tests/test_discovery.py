"""A configuration, a traffic mix and a metric added as new files, with
entries in BENCHMARK.json, are found by name; nothing else is edited."""
import json
import os
import shutil

from flamebench import harness, traffic as T

ROOT = harness.ROOT


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "flamebench"), root / "flamebench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (root / "flamebench/configs/tiny-new.json").write_text(
        (root / "flamebench/tests/data/tiny.json").read_text())
    (root / "flamebench/traffic/burst.json").write_text(
        (root / "flamebench/tests/data/tiny_session.json").read_text())
    (root / "flamebench/limits/tiny-new.burst.json").write_text(
        json.dumps({"score_gap": 0.5}))
    (root / "flamebench/metrics/burst.depth.py").write_text(
        "def read(rec):\n    return 42.0 + len(rec['requests'])\n")
    bench["configs"].append({"name": "tiny-new", "source": "test",
                             "file": "flamebench/configs/tiny-new.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-new.burst",
                               "config": "tiny-new", "traffic": "burst",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "burst.depth", "unit": "1",
                               "better": "lower",
                               "source": "program_counter",
                               "layer": "Admission", "moves": "setup_s",
                               "workloads": ["tiny-new.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    _, cell, cfg = harness.load_cell("tiny-new.burst", str(root))
    assert cfg["file"] == "flamebench/configs/tiny-new.json"
    mix = T.load(cell["traffic"], str(root / "flamebench"))
    assert mix["users"] == 400
    e2e = [m["name"] for m in harness.cell_metrics(bench, "tiny-new.burst",
                                                   False)]
    assert e2e == ["setup_s"]
    layer = [m["name"] for m in harness.cell_metrics(bench,
                                                     "tiny-new.burst", True)]
    assert layer == ["burst.depth"]
    read = harness.reader("burst.depth", str(root))
    assert read({"requests": [1, 2]}) == 44.0
    # the cells already there keep exactly their metrics
    old = [m["name"] for m in harness.cell_metrics(
        bench, "climber-base.session", True)]
    assert "burst.depth" not in old and "step_mfu.session" in old


def test_every_metric_of_every_cell_has_a_reader_and_limits_exist():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for cell in bench["workloads"]:
        for trace in (False, True):
            for m in harness.cell_metrics(bench, cell["name"], trace):
                assert callable(harness.reader(m["name"]))
        limits = harness.load_json(ROOT, "flamebench", "limits",
                                   cell["name"] + ".json")
        T.load(cell["traffic"])
        assert "score_gap" in limits
