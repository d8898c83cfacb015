"""A configuration, a traffic mix, a metric and a model family added as
new files, with entries in BENCHMARK.json, are found by name; nothing else
is edited.  The generic modules name no family."""
import glob
import gzip
import json
import os
import re
import shutil
import time

import pytest

from flamebench import harness, trace as TR, traffic as T

ROOT = harness.ROOT
DATA = os.path.join(ROOT, "flamebench", "tests", "data")


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


# ---------------------------------------------------------------------------
# a second model family, as new files only
# ---------------------------------------------------------------------------

#: a family whose program is a tiny Climber, under keys of its own
TOY_FAMILY = '''"""Test family: a tiny Climber described by keys of its own."""
from flamebench.families import Kernel
from flamebench.families import climber as base


def _program_model(model):
    return {"n_layers": model["depth"], "d_model": model["width"],
            "n_heads": model["heads"], "n_kv_heads": model["heads"],
            "head_dim": model["head_width"], "d_ff": model["ffn"],
            "vocab_size": model["vocab_size"], "rope_theta": 1e6,
            "norm": "layernorm", "activation": "gelu",
            "climber": {"num_blocks": model["blocks"],
                        "layers_per_block": model["depth"],
                        "num_tasks": model["tasks"],
                        "num_experts_head": model["experts"],
                        "adaptive_temperature": True}}


def program_config(conf):
    return base.program_config({"model": _program_model(conf["model"])})


def layout(model):
    return base.layout(_program_model(model))


def reference_row(req, n_history):
    return base.reference_row(req, n_history)


def reference_scores(params, model, rows, max_slate, *, lowp=False):
    return base.reference_scores(params, _program_model(model), rows,
                                 max_slate, lowp=lowp)


def request_flops(model, n_history, m, *, new_user, grew):
    per_token = 2 * (4 * model["width"] ** 2
                     + 2 * model["width"] * model["ffn"])
    tokens = m + (n_history if new_user else 0)
    return float(model["blocks"] * model["depth"] * tokens * per_token)


def toy_work(call, model, n_history, counters):
    q = call["rows"] * call["q_rows"] * call["heads"] * model["head_width"]
    s = min(call["s_pad"], n_history // model["blocks"] + 1)
    kv = s * call["heads"] * model["head_width"] * 2 * call["kv_bytes"]
    return 4.0 * q * (s + 1), float(kv + 4 * q * 2)


KERNELS = {"toy_score": Kernel("%_fused_kernel_call", base.kernel_call,
                               toy_work)}
'''

TOY_METRIC = '''"""toy_score's share of its roofline over the traced window."""
from flamebench import stats


def read(rec):
    return stats.roofline_share(rec, "toy_score")
'''


def _tiny_conf(**over):
    with open(os.path.join(DATA, "tiny.json")) as f:
        conf = json.load(f)
    conf.update(over)
    return conf


def _toy_conf():
    conf = _tiny_conf(family="toy", source="test")
    conf["model"] = {"vocab_size": 5000, "width": 64, "heads": 4,
                     "head_width": 16, "ffn": 256, "blocks": 2, "depth": 2,
                     "tasks": 3, "experts": 4}
    return conf


def _files(root):
    out = {}
    for p in glob.glob(os.path.join(root, "**", "*"), recursive=True):
        if os.path.isfile(p) and "__pycache__" not in p:
            with open(p, "rb") as f:
                out[os.path.relpath(p, root)] = f.read()
    return out


def _copy(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "flamebench"), root / "flamebench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return root


def _add_cell(bench, name, config, traffic):
    bench["configs"].append({"name": config, "source": "test",
                             "file": f"flamebench/configs/{config}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": traffic, "chips": 1,
                               "why": "test"})


def test_second_family_joins_as_new_files_only(tmp_path):
    root = _copy(tmp_path)
    before = _files(str(root))
    bench = json.loads(before["BENCHMARK.json"])
    added = {
        "flamebench/families/toy.py": TOY_FAMILY,
        "flamebench/configs/toy.json": json.dumps(_toy_conf()),
        "flamebench/traffic/toy_mix.json":
            before["flamebench/tests/data/tiny_closed.json"].decode(),
        "flamebench/limits/toy.mix.json": json.dumps({"score_gap": 0.025}),
        "flamebench/metrics/toy_score_roofline.mix.py": TOY_METRIC,
    }
    for rel, text in added.items():
        (root / rel).write_text(text)
    _add_cell(bench, "toy.mix", "toy", "toy_mix")
    next(m for m in bench["end_to_end"]
         if m["name"] == "items_per_s")["workloads"].append("toy.mix")
    bench["per_layer"].append({
        "name": "toy_score_roofline.mix", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "Kernel: toy_score",
        "moves": "items_per_s", "workloads": ["toy.mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    line = harness.run("toy.mix", 2**31 + 19, 2.0, False,
                       t_start=time.perf_counter(), root=str(root))
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"items_per_s", "setup_s"}
    assert [m["name"] for m in harness.cell_metrics(bench, "toy.mix", True)
            ] == ["toy_score_roofline.mix"]

    # the toy's kernel, read from the recorded trace through its metric
    fam = harness.family(_toy_conf(), "toy.json", str(root))
    raw = tmp_path / "small.xplane.pb"
    with gzip.open(os.path.join(DATA, "trace_small.xplane.pb.gz")) as f:
        raw.write_bytes(f.read())
    reduced = TR.reduce(str(raw), fam.KERNELS)
    assert {k["kernel"] for k in reduced["kernels"]} == {"toy_score"}
    rec = {"trace": reduced, "family": fam, "model": _toy_conf()["model"],
           "n_history": 512, "trace_counters": {},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    share = harness.reader("toy_score_roofline.mix", str(root))(rec)
    assert 0.0 < share < 100.0
    assert harness.reader("fused_score_roofline.session", str(root))(
        rec) is None

    # nothing that was there is edited; BENCHMARK.json only gained entries
    after = _files(str(root))
    assert set(after) - set(before) == set(added)
    for rel, data in before.items():
        if rel != "BENCHMARK.json":
            assert after[rel] == data, rel
    old = json.loads(before["BENCHMARK.json"])
    new = json.loads(after["BENCHMARK.json"])
    for key, value in old.items():
        if isinstance(value, list):
            new[key] = new[key][:len(value)]
    for m in new["end_to_end"] + new["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [w for w in m["workloads"] if w != "toy.mix"]
    assert new == old


@pytest.mark.parametrize("config,family,says", [
    ("nofam", None, "no model family"),
    ("unknown", "nope", "has no module"),
    ("half", "half", "lacks"),
])
def test_a_config_without_a_known_family_fails_naming_its_file(
        tmp_path, config, family, says):
    root = _copy(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    conf = _tiny_conf()
    conf.pop("family")
    if family is not None:
        conf["family"] = family
    (root / f"flamebench/configs/{config}.json").write_text(json.dumps(conf))
    (root / "flamebench/families/half.py").write_text(
        "def layout(model):\n    return {}\n")
    _add_cell(bench, f"{config}.session", config, "session")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(ValueError) as e:
        harness.run(f"{config}.session", 1, 1.0, False, t_start=0.0,
                    root=str(root))
    assert f"flamebench/configs/{config}.json" in str(e.value)
    assert says in str(e.value)


#: modules that serve every family alike
GENERIC = sorted(glob.glob(os.path.join(ROOT, "flamebench", "*.py"))
                 + glob.glob(os.path.join(ROOT, "flamebench", "metrics",
                                          "*.py"))
                 + [os.path.join(ROOT, "flamebench", "families",
                                 "__init__.py")])


@pytest.mark.parametrize("path", GENERIC,
                         ids=[os.path.relpath(p, ROOT) for p in GENERIC])
def test_generic_modules_name_no_family(path):
    with open(path) as f:
        text = f.read()
    found = re.findall(r"(?i)climber|side_features|_fused_kernel_call", text)
    assert not found, (path, sorted(set(found)))
