"""Benchmark harness — one module per paper table.

  bench_pda       Table 3: PDA feature-pipeline ablation (measured)
  bench_fke       Table 4: FKE engine-build ablation (measured + modeled)
  bench_dso       Table 5: DSO vs implicit-shape mixed traffic (measured)
  bench_serving   API v2 coalesced-vs-per-request A/B; emits BENCH_serving.json
  bench_roofline  assignment roofline table from dry-run artifacts

Each prints human tables plus ``name,us_per_call,derived`` CSV lines.

    PYTHONPATH=src python -m benchmarks.run [--only pda|fke|dso|serving|roofline]
"""
import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    choices=[None, "pda", "fke", "dso", "serving",
                             "roofline"])
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bench_dso, bench_fke, bench_pda, bench_roofline,
                            bench_serving)
    jobs = {"pda": bench_pda.main, "fke": bench_fke.main,
            "dso": bench_dso.main, "serving": bench_serving.main,
            "roofline": bench_roofline.main}
    failed = []
    for name, fn in jobs.items():
        if args.only and name != args.only:
            continue
        try:
            fn()
        except Exception:  # noqa: BLE001
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"FAILED benches: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
