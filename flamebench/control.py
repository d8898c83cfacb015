"""The control of a cell's correctness check: the family's plain reference
put in the program's place, in the precision below the served one
(``reference_scores(..., lowp=True)``: float8 e4m3 operands for every
matrix product, against bfloat16 served).  It must come out not correct;
the benchmark's own runs never run it.

    python3 flamebench/control.py --workload <cell> --seeds 1,2,3

For each seed: the cell's weights and traffic from that seed, a sample of
the window's requests as large as a run checks (the largest slate among
them), and the check's number with the control's answers: the widest gap
between the lower-precision and the float32 answers.  One JSON line per
seed.
"""
import argparse
import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]


def window_sample(tr, mix: dict, seed: int):
    """The requests a run of this seed would send in its window (closed
    loop: the first ones by index after the warm requests), sampled as a
    run samples them."""
    from flamebench import harness

    if tr.loop == "open":
        reqs = [r for r in tr.window if r.due < tr.seconds]
    else:
        reqs = [tr.closed(i) for i in range(4 * int(mix["check"]) + 64)]
    recs = [{"req": r, "m": len(r.candidates)} for r in reqs]
    return harness.sample(recs, int(mix["check"]), seed,
                          key=lambda r: r["m"])


def reading(cell: str, seed: int, *, root: str = ROOT, conf=None,
            mix=None) -> dict:
    import jax

    from flamebench import harness, traffic as T, weights as W

    bench, c, centry = harness.load_cell(cell, root)
    conf, fam = harness.config_and_family(centry, root, conf)
    mix = mix or T.load(c["traffic"], os.path.join(root, "flamebench"))
    model = conf["model"]
    params = jax.block_until_ready(W.make_params(fam.layout(model), seed))
    tr = T.Traffic(mix, n_history=conf["n_history"],
                   vocab=model["vocab_size"], seed=seed,
                   seconds=float(bench["run_seconds"]))
    picked = window_sample(tr, mix, seed)
    value = harness.score_gap(fam, params, model, conf["n_history"], picked,
                              int(conf["max_slate"]), lowp=True)
    return {"workload": cell, "seed": seed, "check": "score_gap",
            "value": value, "requests": len(picked)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    # the TPU runtime logs into TMPDIR, not a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(
        tempfile.gettempdir(), "flamebench-tpu-logs"))
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    if jax.devices()[0].platform != "tpu":
        print("[flamebench] the control runs on the TPU chip",
              file=sys.stderr)
        return 3
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(reading(args.workload, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
