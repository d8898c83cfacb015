"""Run one cell of ``BENCHMARK.json`` once.

Everything a cell needs is found by name: its configuration
(``configs/<config>.json``), the model family that configuration names
(``families/<family>.py``), its traffic mix (``traffic/<traffic>.json``),
its correctness limits (``limits/<cell>.json``) and one reader per metric
(``metrics/<metric>.py``).  A run builds the weights of the family's layout
from the seed, builds the engine through ``create_engine("flame", ...)``,
sends the mix's warm traffic untimed, measures for ``seconds``, checks what
the window served against the family's plain reference, and returns the
result line.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import re
import sys
import tempfile
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from flamebench import families, traffic as T, weights as W, work
from flamebench import trace as TR

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: seconds a run waits past the window for the requests due in it
DRAIN_LIMIT_S = 60.0
#: rows per reference call (the reference runs in blocks of requests)
REF_BLOCK = 8


def log(msg: str) -> None:
    print(f"[flamebench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT):
    """(benchmark, cell, config entry) for the cell ``name``."""
    bench = load_json(root, "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return bench, cell, cfg


def cell_metrics(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics, or
    with ``trace`` its per-layer metrics."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moves = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in moves
                             else [])]


def reader(metric: str, root: str = ROOT
           ) -> Callable[[dict], Optional[float]]:
    """The ``read(record)`` function of ``metrics/<metric>.py``."""
    path = os.path.join(root, "flamebench", "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "flamebench_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def family(conf: dict, where: str, root: str = ROOT):
    """The module ``families/<family>.py`` that the configuration ``conf``
    (read from ``where``) names by its ``"family"`` key; a missing key, a
    missing file or a module short of a role is an error naming them."""
    name = conf.get("family")
    if not isinstance(name, str) or not re.fullmatch(r"[A-Za-z0-9_]+", name):
        raise ValueError(f"{where}: no model family: the configuration "
                         f"needs a \"family\" key naming a file "
                         f"flamebench/families/<family>.py, has "
                         f"{name!r}")
    path = os.path.join(root, "flamebench", "families", f"{name}.py")
    if not os.path.isfile(path):
        raise ValueError(f"{where}: family {name!r} has no module {path}")
    spec = importlib.util.spec_from_file_location(
        "flamebench_family_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [r for r in families.ROLES if not hasattr(mod, r)]
    if missing:
        raise ValueError(f"{where}: family module {path} lacks {missing}")
    return mod


def config_and_family(centry: dict, root: str = ROOT,
                      conf: Optional[dict] = None):
    """(configuration, family module) of a ``configs`` entry of
    BENCHMARK.json; ``conf``, where given, stands in for its file."""
    where = "the configuration passed in" if conf else centry["file"]
    conf = conf or load_json(root, centry["file"])
    return conf, family(conf, where, root)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

def build_engine(conf: dict, params, bundle):
    from repro.serving import create_engine

    opts = dict(conf["engine"])
    for k in ("buckets", "extend_buckets"):
        if k in opts:
            opts[k] = tuple(opts[k])
    return create_engine("flame", bundle, params,
                         n_history=conf["n_history"], **opts)


def counters(eng) -> Dict[str, float]:
    """The engine's counters and per-(family, bucket) candidate slots."""
    out = {k: float(v) for k, v in eng.metrics().items()
           if isinstance(v, (int, float))}
    for (kind, b), s in dict(eng.dso.slot_count).items():
        out[f"slots_{kind}_b{b}"] = float(s)
    for (kind, b), s in dict(eng.dso.valid_count).items():
        out[f"valid_{kind}_b{b}"] = float(s)
    return out


def delta(a: Dict[str, float], b: Dict[str, float]) -> Dict[str, float]:
    return {k: b[k] - a.get(k, 0.0) for k in b}


# ---------------------------------------------------------------------------
# clients
# ---------------------------------------------------------------------------

class Client:
    """Sends requests, records one log entry per request: when it was due,
    sent and done, whether it succeeded, and what it served."""

    def __init__(self, eng):
        self.eng = eng
        self.log: List[dict] = []
        self._lock = threading.Lock()
        self._idle = threading.Condition(self._lock)
        self._open = 0

    def send(self, r: T.Request, due: float, on_done=None) -> dict:
        from repro.serving.api import RejectedError, ServeRequest

        rec = {"due": due, "sent": time.perf_counter(), "done": None,
               "ok": False, "req": r, "m": len(r.candidates), "out": None,
               "queue_s": None, "error": None}
        with self._lock:
            self.log.append(rec)
            self._open += 1
        try:
            fut = self.eng.submit(ServeRequest(
                history=r.history, candidates=r.candidates,
                user_id=r.user_id), timeout=0)
        except RejectedError as e:
            self._finish(rec, None, e, on_done)
            return rec

        def done(f):
            try:
                resp = f.result()
            except BaseException as e:  # noqa: BLE001 — recorded, judged
                self._finish(rec, None, e, on_done)
            else:
                self._finish(rec, resp, None, on_done)
        fut.add_done_callback(done)
        return rec

    def _finish(self, rec, resp, err, on_done):
        rec["done"] = time.perf_counter()
        if resp is not None:
            rec["ok"] = True
            rec["out"] = resp.output
            rec["queue_s"] = resp.timings.get("queue_s")
        else:
            rec["error"] = repr(err)
        with self._lock:
            self._open -= 1
            self._idle.notify_all()
        if on_done is not None:
            on_done()

    def wait_idle(self, timeout: float) -> bool:
        end = time.perf_counter() + timeout
        with self._lock:
            while self._open:
                left = end - time.perf_counter()
                if left <= 0:
                    return False
                self._idle.wait(left)
        return True


def closed_loop(client: Client, source, concurrency: int,
                stop: threading.Event) -> None:
    """Keep ``concurrency`` requests in flight, from ``source(i)`` until it
    returns None or ``stop`` is set."""
    slots = threading.Semaphore(concurrency)
    i = 0
    while not stop.is_set():
        if not slots.acquire(timeout=0.05):
            continue
        if stop.is_set():
            slots.release()
            break
        r = source(i)
        if r is None:
            slots.release()
            break
        client.send(r, time.perf_counter(), on_done=slots.release)
        i += 1


def send_warm(eng, tr: T.Traffic, mix: dict) -> int:
    """Send the mix's warm requests, untimed, closed loop at its
    ``warm_concurrency``; raise if any failed.  Returns how many."""
    warm = Client(eng)
    it = iter(tr.warm)
    closed_loop(warm, lambda i: next(it, None),
                int(mix["warm_concurrency"]), threading.Event())
    warm.wait_idle(600)
    bad = [w["error"] for w in warm.log if not w["ok"]]
    if bad:
        raise RuntimeError(f"warm traffic failed: {bad[:3]}")
    return len(warm.log)


def open_loop(client: Client, reqs: List[T.Request], t0: float,
              stop: threading.Event) -> None:
    for r in reqs:
        due = t0 + r.due
        left = due - time.perf_counter()
        if left > 0:
            time.sleep(left)
        if stop.is_set():
            break
        client.send(r, due)


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def sample(done: List[dict], n: int, seed: int, key) -> List[dict]:
    """``n`` finished requests drawn from the seed, the largest by ``key``
    among them."""
    if not done:
        return []
    big = max(range(len(done)), key=lambda i: key(done[i]))
    rng = np.random.default_rng([int(seed) % 2**64, 0xC4EC])
    rest = [i for i in rng.permutation(len(done)) if i != big][:n - 1]
    return [done[big]] + [done[i] for i in rest]


def _ref_rows(fam, params, model, rows, max_slate, *,
              lowp=False) -> List[np.ndarray]:
    """The family's reference answers for its rows, run in blocks of
    REF_BLOCK rows (the last filled up with its first row) at
    ``max_slate`` candidates, so every run reuses one program."""
    out = []
    for i in range(0, len(rows), REF_BLOCK):
        blk = rows[i:i + REF_BLOCK]
        blk = blk + [blk[0]] * (REF_BLOCK - len(blk))
        out += fam.reference_scores(params, model, blk, max_slate,
                                    lowp=lowp)
    return out[:len(rows)]


def score_gap(fam, params, model, n_history, served, max_slate, *,
              lowp=False) -> float:
    """Widest |served - reference| answer over the requests; with ``lowp``
    the lower-precision reference stands in for the served answers (the
    control)."""
    rows = [fam.reference_row(s["req"], n_history) for s in served]
    refs = _ref_rows(fam, params, model, rows, max_slate)
    if lowp:
        got = _ref_rows(fam, params, model, rows, max_slate, lowp=True)
    else:
        got = [np.asarray(s["out"], np.float32) for s in served]
    return max(float(np.abs(a - b).max()) for a, b in zip(got, refs))


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, root: str = ROOT, conf: Optional[dict] = None,
        mix: Optional[dict] = None, limits: Optional[dict] = None,
        trace_seconds: Optional[float] = None,
        keep_trace: Optional[str] = None) -> dict:
    """One run of one cell; returns the result line as a dict."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import build_model

    bench, cell, centry = load_cell(cell_name, root)
    conf, fam = config_and_family(centry, root, conf)
    mix = mix or T.load(cell["traffic"], os.path.join(root, "flamebench"))
    limits = limits or load_json(root, "flamebench", "limits",
                                 f"{cell_name}.json")
    metrics = cell_metrics(bench, cell_name, trace)
    enable_compile_cache()
    dev = jax.devices()[0]
    n_history = conf["n_history"]
    model = conf["model"]

    bundle = build_model(fam.program_config(conf))
    t = time.perf_counter()
    params = jax.block_until_ready(W.make_params(fam.layout(model), seed))
    W.check_layout(params, jax.eval_shape(lambda k: bundle.init(k)[0],
                                          jax.random.key(0)))
    log(f"weights from seed {seed} in {time.perf_counter() - t:.2f}s")
    tr = T.Traffic(mix, n_history=n_history, vocab=model["vocab_size"],
                   seed=seed, seconds=seconds)
    t = time.perf_counter()
    eng = build_engine(conf, params, bundle)
    log(f"engine: {len(eng.dso.compiled)} executors in "
        f"{time.perf_counter() - t:.2f}s")
    try:
        t = time.perf_counter()
        n = send_warm(eng, tr, mix)
        log(f"warm: {n} requests in {time.perf_counter() - t:.2f}s")
        gc.collect()

        win = measure(eng, tr, mix, seconds, trace=trace,
                      trace_seconds=trace_seconds, keep_trace=keep_trace,
                      kernels=fam.KERNELS)
        peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    finally:
        eng.shutdown()
    del eng
    gc.collect()

    record = dict(win, cell=cell_name, config=conf, family=fam, mix=mix,
                  model=model, n_history=n_history, seconds=seconds,
                  setup_s=win["t0"] - t_start,
                  peaks=work.peaks(dev.device_kind)
                  if dev.platform == "tpu" else None)
    values = {}
    for m in metrics:
        v = reader(m["name"], root)(record)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # ---- correctness, after the window, the engine freed ----
    window = record["requests"]
    hung = sum(1 for r in window if r["done"] is None)
    errors = [r["error"] for r in window
              if r["error"] and "Rejected" not in r["error"]
              and "Shed" not in r["error"] and "QueueFull" not in r["error"]]
    failed = sum(1 for r in window if not r["ok"])
    done = [r for r in window if r["ok"]]
    t = time.perf_counter()
    picked = sample(done, int(mix["check"]), seed, key=lambda r: r["m"])
    gap = score_gap(fam, params, model, n_history, picked,
                    int(conf["max_slate"])) if picked else float("inf")
    log(f"reference over {len(picked)} requests in "
        f"{time.perf_counter() - t:.2f}s")
    checks = {
        "score_gap": {"value": gap, "limit": float(limits["score_gap"])},
        "hung": {"value": hung, "limit": 0},
        "errors": {"value": len(errors), "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    if errors:
        log(f"errors: {errors[:3]}")
    log(f"window: {len(window)} requests due, {failed} failed, {hung} "
        f"unfinished, {record['compiles']} compiles inside the window")
    line = {
        "correct": bool(correct),
        "attempted": len(window),
        "failed": failed,
        "metrics": values,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": peak},
    }
    if trace and record["trace"] is not None:
        s = record["trace"]
        line["device"]["busy_s"] = s["busy_s"]
        line["device"]["window_s"] = s["window_s"]
        line["breakdown"] = {"device_ops": s["device_ops"],
                             "idle_gaps": s["idle_gaps"]}
    line["checks"] = checks
    for k, c in checks.items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    return line


def measure(eng, tr: T.Traffic, mix: dict, seconds: float, *,
            trace: bool = False, trace_seconds: Optional[float] = None,
            keep_trace: Optional[str] = None,
            kernels: Optional[dict] = None) -> dict:
    """Drive the window: open-loop arrivals on their schedule, or a closed
    loop at the mix's concurrency, for ``seconds``; then wait (at most
    DRAIN_LIMIT_S) for the requests due in it.  Returns the request log of
    the window, its bounds, the counters' window delta, compilations seen
    inside it and, with ``trace``, the reduced trace of its middle, with
    the calls of ``kernels`` (the family's ``KERNELS``)."""
    client = Client(eng)
    stop = threading.Event()
    before = counters(eng)
    compiles = _CompileCounter()
    traced: dict = {}
    t0 = time.perf_counter() + 0.05
    if tr.loop == "open":
        th = threading.Thread(target=open_loop,
                              args=(client, tr.window, t0, stop))
    else:
        th = threading.Thread(
            target=closed_loop,
            args=(client, tr.closed, int(mix["concurrency"]), stop))
    tracer = None
    if trace:
        d = trace_seconds or min(3.0, seconds / 2)
        tracer = threading.Thread(target=_trace_window, args=(
            eng, t0 + (seconds - d) / 2, d, traced, keep_trace,
            kernels or {}))
    while time.perf_counter() < t0:
        time.sleep(0.001)
    th.start()
    if tracer is not None:
        tracer.start()
    t1 = t0 + seconds
    time.sleep(max(0.0, t1 - time.perf_counter()))
    if tr.loop == "closed":
        stop.set()
    if tracer is not None:
        tracer.join()
    # the requests due in the window must finish; open-loop load goes on
    # meanwhile, so the window's last requests still meet it
    end = t1 + DRAIN_LIMIT_S
    while time.perf_counter() < end:
        due = [r for r in client.log if r["due"] <= t1]
        if all(r["done"] is not None for r in due):
            break
        time.sleep(0.01)
    stop.set()
    th.join()
    client.wait_idle(max(1.0, end - time.perf_counter()))
    n_compiles = compiles.stop()
    return {"window": (t0, t1), "t0": t0,
            "requests": [r for r in client.log if r["due"] <= t1],
            "counters": delta(before, counters(eng)),
            "compiles": n_compiles,
            "trace": traced.get("summary"),
            "trace_counters": traced.get("counters"),
            "trace_window": traced.get("window")}


class _CompileCounter:
    """Counts XLA compilations (``backend_compile`` events) until stop."""

    def __init__(self):
        from jax._src import monitoring

        self.n = 0
        self._on = True

        def on_event(event, duration_secs, **kw):
            if self._on and event.endswith("backend_compile_duration"):
                self.n += 1
        self._cb = on_event
        monitoring.register_event_duration_secs_listener(on_event)

    def stop(self) -> int:
        self._on = False
        return self.n


def _trace_window(eng, at: float, d: float, out: dict,
                  keep: Optional[str], kernels: dict) -> None:
    """Trace ``d`` seconds from ``at``: profiler on, a host annotation
    marking the window, profiler off; then reduce the trace."""
    import shutil

    import jax

    left = at - time.perf_counter()
    if left > 0:
        time.sleep(left)
    tmp = keep or tempfile.mkdtemp(prefix="flamebench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    c0 = counters(eng)
    w0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(TR.WINDOW):
        time.sleep(d)
    w1 = time.perf_counter()
    c1 = counters(eng)
    jax.profiler.stop_trace()
    files = []
    for dirpath, _, names in os.walk(tmp):
        files += [os.path.join(dirpath, n) for n in names
                  if n.endswith(".xplane.pb")]
    try:
        out["summary"] = TR.reduce(max(files, key=os.path.getmtime),
                                   kernels)
        out["counters"] = delta(c0, c1)
        out["window"] = (w0, w1)
    except (ValueError, OSError) as e:
        log(f"trace not read: {e}")
    finally:
        if keep is None:
            shutil.rmtree(tmp, ignore_errors=True)
