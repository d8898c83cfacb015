"""The five stage readers (``pda.features_ms``, ``pool.host_ms``,
``dso.queue_ms``, ``dso.host_ms``, ``dso.run_ms``): their value on a
made-up record, None with a zero denominator or without the program's
counters, and numbers from a whole traced run at a test size."""
import json
import os
import time

import pytest

from flamebench import harness

DATA = os.path.join(os.path.dirname(__file__), "data")
STAGE_METRICS = ("pda.features_ms", "pool.host_ms", "dso.queue_ms",
                 "dso.host_ms", "dso.run_ms")

COUNTERS = {
    "features_s": 0.6, "features_n": 200.0,
    "pool_lookup_s": 0.3, "pool_put_s": 0.2, "pool_lookup_n": 500.0,
    "dso_queue_delay_s": 4.0, "dso_queue_delay_n": 1000.0,
    "dso_stack_s": 1.5, "dso_readback_s": 0.5, "dso_scatter_s": 1.0,
    "dso_launch_s": 2.0, "dso_wait_s": 10.0, "dso_dispatches": 600.0,
}
#: metric -> (its value on COUNTERS, the counter that is its denominator)
EXPECTED = {
    "pda.features_ms": (1e3 * 0.6 / 200, "features_n"),
    "pool.host_ms": (1e3 * 0.5 / 500, "pool_lookup_n"),
    "dso.queue_ms": (1e3 * 4.0 / 1000, "dso_queue_delay_n"),
    "dso.host_ms": (1e3 * 3.0 / 600, "dso_dispatches"),
    "dso.run_ms": (1e3 * 12.0 / 600, "dso_dispatches"),
}


@pytest.mark.parametrize("name", STAGE_METRICS)
def test_reader_value(name):
    value, _ = EXPECTED[name]
    assert harness.reader(name)({"counters": dict(COUNTERS)}) \
        == pytest.approx(value)


@pytest.mark.parametrize("name", STAGE_METRICS)
def test_reader_none_on_zero_denominator(name):
    _, denom = EXPECTED[name]
    c = dict(COUNTERS, **{denom: 0.0})
    assert harness.reader(name)({"counters": c}) is None


@pytest.mark.parametrize("name", STAGE_METRICS)
def test_reader_none_without_the_program_counters(name):
    """A program without the stage counters (only the older DSO and pool
    counts) reads nothing, and does not raise."""
    c = {"dso_dispatches": 600.0, "dso_queue_delay_ms": 4.0,
         "pool_hits": 10.0, "pool_misses": 5.0, "requests": 15.0}
    assert harness.reader(name)({"counters": c}) is None


def test_stage_metrics_listed_for_both_cells():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    for cell in ("climber-base.session", "climber-long.session"):
        names = {m["name"] for m in harness.cell_metrics(bench, cell, True)}
        assert set(STAGE_METRICS) <= names


def test_whole_traced_run_reads_every_stage_metric():
    with open(os.path.join(DATA, "tiny.json")) as f:
        conf = json.load(f)
    with open(os.path.join(DATA, "tiny_closed.json")) as f:
        mix = json.load(f)
    cell = "climber-base.session"
    limits = harness.load_json(harness.ROOT, "flamebench", "limits",
                               f"{cell}.json")
    line = harness.run(cell, 2**31 + 11, 2.0, True,
                       t_start=time.perf_counter(), root=harness.ROOT,
                       conf=conf, mix=mix, limits=limits)
    assert line["correct"], line["checks"]
    for name in STAGE_METRICS:
        got = line["metrics"].get(name)
        assert got is not None, (name, sorted(line["metrics"]))
        assert got["unit"] == "ms" and got["value"] >= 0.0
