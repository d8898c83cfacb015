"""Run one cell of the chip benchmark once and print its result line.

    python3 flamebench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Run from the root of a checkout, on a machine with the TPU chips the cell
asks for.  Without them it exits non-zero and prints no result.  With
``--trace 0`` the line carries the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from a profiler trace of part of
the window.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks`` (each compared number
beside its limit, also the last lines on standard error).
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
# the package is imported as ``flamebench``: its module names (``trace``)
# must not shadow the standard library's from the script's directory
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-seconds", type=float, default=None,
                    help="length of the traced part of the window")
    ap.add_argument("--keep-trace", default=None,
                    help="directory to keep the raw trace in")
    args = ap.parse_args(argv)
    # the TPU runtime logs into TMPDIR, not a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(
        tempfile.gettempdir(), "flamebench-tpu-logs"))
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from flamebench import harness

    _, cell, _ = harness.load_cell(args.workload, ROOT)
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        harness.log(f"no usable JAX backend: {e}")
        return 3
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        harness.log(f"needs {cell['chips']} TPU chip(s); JAX has "
                    f"{len(devs)} {devs[0].platform} device(s)")
        return 3
    line = harness.run(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=T_START, root=ROOT,
                       trace_seconds=args.trace_seconds,
                       keep_trace=args.keep_trace)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
