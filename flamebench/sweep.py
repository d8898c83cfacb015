"""Find the highest open-loop rate a cell's engine sustains under its
traffic.

    python3 flamebench/sweep.py --workload <cell> --seed <n> \\
        --rates 20,40,60 --seconds 15

One process and one set-up (weights, engine, the mix's warm traffic), then
one window per rate, lowest first, each with the cell's own traffic sent
open loop (Poisson arrivals) at that rate.  Prints one JSON line per rate: offered and completed requests per
second, items/s, p50/p99 latency from the due time, how late the
generator sent, and whether the backlog grew (p99 of the window's last
third over its first third).  Last, one line with the knee (``knee``):
the highest rate, among those swept, up to which no request failed and the
median latency stayed within ``KNEE_P50`` times the lightest rate's.  A
cell below the knee runs at about four fifths of it.  Needs the TPU chip;
without it exits non-zero.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]


#: how far the median latency may rise over the lightest rate's at the knee
KNEE_P50 = 2.0


def knee(lines: list):
    """The highest swept rate up to which every rate had no failure and a
    median latency within KNEE_P50 x the lightest rate's; None if even the
    lightest fails."""
    best, base = None, None
    for ln in sorted(lines, key=lambda x: x["rate_per_s"]):
        p50 = ln["p50_ms"]
        if base is None:
            base = p50
        if ln["failed"] or p50 is None or base is None \
                or p50 > KNEE_P50 * base:
            break
        best = ln["rate_per_s"]
    return best


def summarize(win: dict, rate: float, seconds: float) -> dict:
    from flamebench import stats

    reqs = win["requests"]
    lat = stats.latencies_ms(win)
    t0, _ = win["window"]
    thirds = [[1e3 * (r["done"] - r["due"]) if r["ok"] else float("inf")
               for r in reqs if lo <= (r["due"] - t0) / seconds < hi]
              for lo, hi in ((0, 1 / 3), (2 / 3, 1.01))]
    p_first = stats.percentile(thirds[0], 99)
    p_last = stats.percentile(thirds[1], 99)
    done = stats.completed_in_window(win)
    late = sorted(r["sent"] - r["due"] for r in reqs)
    return {
        "rate_per_s": rate,
        "offered": len(reqs) / seconds,
        "completed_per_s": len(done) / seconds,
        "items_per_s": sum(r["m"] for r in done) / seconds,
        "p50_ms": stats.percentile(lat, 50),
        "p99_ms": stats.percentile(lat, 99),
        "failed": sum(1 for r in reqs if not r["ok"]),
        "send_late_p99_ms": 1e3 * late[int(0.99 * (len(late) - 1))]
        if late else None,
        "backlog_growth": (p_last / p_first) if p_first and p_last
        else None,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--out", default=None, help="also append lines here")
    args = ap.parse_args(argv)
    # the TPU runtime logs into TMPDIR, not a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(
        tempfile.gettempdir(), "flamebench-tpu-logs"))
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from flamebench import harness, traffic as T, weights as W
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import build_model

    _, cell, centry = harness.load_cell(args.workload, ROOT)
    if jax.devices()[0].platform != "tpu":
        harness.log("needs a TPU chip")
        return 3
    conf, fam = harness.config_and_family(centry, ROOT)
    mix = T.load(cell["traffic"], HERE)
    enable_compile_cache()
    model = conf["model"]
    bundle = build_model(fam.program_config(conf))
    params = W.make_params(fam.layout(model), args.seed)
    eng = harness.build_engine(conf, params, bundle)
    try:
        first = T.Traffic(mix, n_history=conf["n_history"],
                          vocab=model["vocab_size"], seed=args.seed,
                          seconds=args.seconds)
        harness.send_warm(eng, first, mix)
        harness.log(f"set-up {time.perf_counter() - T_START:.1f}s")
        lines = []
        for rate in (float(x) for x in args.rates.split(",")):
            m = dict(mix, loop="open", rate_per_s=rate,
                     drain_s=mix.get("drain_s", 0.0))
            tr = T.Traffic(m, n_history=conf["n_history"],
                           vocab=model["vocab_size"], seed=args.seed,
                           seconds=args.seconds)
            win = harness.measure(eng, tr, m, args.seconds)
            lines.append(dict(summarize(win, rate, args.seconds),
                              workload=args.workload,
                              compiles=win["compiles"]))
            print(json.dumps(lines[-1]), flush=True)
        result = [json.dumps(x) for x in lines]
        result.append(json.dumps({"workload": args.workload,
                                  "knee": knee(lines)}))
        print(result[-1], flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write("\n".join(result) + "\n")
    finally:
        eng.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
