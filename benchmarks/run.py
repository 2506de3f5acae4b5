# One function per paper table/figure. Prints
# ``name,us_per_call,stable,derived`` CSV rows.
# ``python -m benchmarks.run [--quick] [--json PATH]``.
#
# ``--json PATH`` additionally writes the suite results as JSON — the
# tracked perf trajectory (CI diffs a fresh run against the committed
# BENCH_quick.json and gates on stable-tagged rows).  Schema: a list of
# suite objects
#   {"suite": str, "rows": [{"name": str, "ms": float, "stable": bool,
#                            "note": str}],
#    "meta": {"elapsed_s": float, "quick": bool, "backend": str,
#             "error": str | absent}}
# ``stable`` marks rows whose timing is run-stable on this container
# (PIM-paced rows); only those may be regression-gated — see
# tools/bench_compare.py.

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback


def _parse_row(line: str) -> dict:
    """'name,us_per_call,stable,derived' CSV row (benchmarks.common.row)
    -> {name, ms, stable, note}."""
    name, us, stable, note = line.split(",", 3)
    return {"name": name, "ms": float(us) / 1e3,
            "stable": bool(int(stable)), "note": note}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write suite results as JSON")
    args = ap.parse_args()

    from benchmarks import (bench_recall, bench_e2e, bench_breakdown,
                            bench_multiplierless, bench_perfmodel,
                            bench_loadbalance, bench_scaling, bench_kernels,
                            bench_dse, bench_serving, bench_pareto)
    benches = {
        "recall": bench_recall,            # §V-A accuracy constraint
        "e2e": bench_e2e,                  # Fig. 6/7
        "breakdown": bench_breakdown,      # Fig. 8
        "multiplierless": bench_multiplierless,   # Fig. 10a
        "perfmodel": bench_perfmodel,      # Fig. 10b
        "loadbalance": bench_loadbalance,  # Fig. 11/12
        "scaling": bench_scaling,          # Fig. 13
        "kernels": bench_kernels,          # Pallas micro-benches
        "dse": bench_dse,                  # §III-C
        "serving": bench_serving,          # online runtime (+ serve/chaos
                                           # fail-operational floor row)
        "pareto": bench_pareto,            # recall/latency frontier sweep
    }
    if args.only:
        names = args.only.split(",")
        benches = {k: v for k, v in benches.items() if k in names}

    import jax

    from repro.util import enable_compile_cache
    enable_compile_cache()
    backend = jax.default_backend()

    print("name,us_per_call,stable,derived")
    failures = []
    suites = []
    for name, mod in benches.items():
        t0 = time.time()
        rows = []
        err = None
        try:
            for line in mod.run(quick=args.quick):
                print(line, flush=True)
                rows.append(_parse_row(line))
        except Exception as e:
            traceback.print_exc()
            err = repr(e)
            failures.append((name, err))
        elapsed = time.time() - t0
        print(f"# [{name}] {elapsed:.1f}s", flush=True)
        meta = {"elapsed_s": round(elapsed, 3), "quick": args.quick,
                "backend": backend}
        if err is not None:
            meta["error"] = err
        suites.append({"suite": name, "rows": rows, "meta": meta})
    if args.json:
        with open(args.json, "w") as f:
            json.dump(suites, f, indent=1)
        print(f"# wrote {args.json} ({len(suites)} suites)", flush=True)
    if failures:
        print(f"# FAILURES: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
