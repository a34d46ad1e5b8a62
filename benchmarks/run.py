# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV and writes the same rows to a machine-readable BENCH_sort.json so
# successive PRs accumulate a perf trajectory.
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# Make `python benchmarks/run.py` work from anywhere: the repo root (and
# src/, for checkouts without `pip install -e .`) must be importable.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller sizes (CI/container friendly)")
    ap.add_argument("--only", default=None, help="comma-separated bench names")
    ap.add_argument("--suite", default=None,
                    help="comma-separated suite names: run only these and "
                         "MERGE their rows into the JSON record (rows from "
                         "suites not run are preserved — unlike --only, "
                         "which skips writing entirely)")
    ap.add_argument("--json", default=None,
                    help="output path for machine-readable rows; default "
                         "BENCH_sort.json, but a --only run does NOT "
                         "write unless --json is passed explicitly (the "
                         "file is the cross-PR perf record and a partial "
                         "row set would clobber it); '' disables")
    args = ap.parse_args()
    if args.suite and args.only:
        ap.error("--suite and --only are mutually exclusive")
    merge = bool(args.suite)
    if args.suite:
        args.only = args.suite
    if args.json is None:
        args.json = "" if (args.only and not merge) else "BENCH_sort.json"

    from repro import compile_cache

    compile_cache.enable()
    from benchmarks import (
        autotune_bench,
        batched_segmented,
        distributed_scaling,
        distribution_robustness,
        dtypes_throughput,
        guard_overhead,
        moe_dispatch,
        sample_size_sweep,
        sort_throughput,
        strategies,
        topk_partial,
    )

    quick = args.quick
    suites = {
        "sort_throughput": lambda: sort_throughput.run(
            sizes=(65536, 262144) if quick else (65536, 262144, 1048576)),
        "sample_size_sweep": lambda: sample_size_sweep.run(
            n=131072 if quick else 524288,
            svals=(16, 64) if quick else (8, 16, 32, 64, 128)),
        "distribution_robustness": lambda: distribution_robustness.run(
            n=65536 if quick else 262144),
        "moe_dispatch": lambda: moe_dispatch.run(
            tokens=4096 if quick else 16384),
        "topk_partial": lambda: topk_partial.run(
            vocab=65536 if quick else 151936),
        "dtypes": lambda: dtypes_throughput.run(
            n=131072 if quick else 1048576),
        "batched": lambda: batched_segmented.run_batched(
            b=64 if quick else 256, l=2048),
        "segmented": lambda: batched_segmented.run_segmented(
            n=65536 if quick else 262144, segments=64 if quick else 256),
        "autotune": lambda: autotune_bench.run(
            n=262144 if quick else 1048576,
            max_trials=8 if quick else 12,
            repeats=2 if quick else 3),
        "strategies": lambda: strategies.run(
            n=262144 if quick else 1048576),
        "distributed": lambda: distributed_scaling.run(
            n_global=65536 if quick else 262144,
            repeats=2 if quick else 3),
        "guard": lambda: guard_overhead.run(
            n=262144 if quick else 1048576,
            repeats=2 if quick else 3),
    }
    only = set(args.only.split(",")) if args.only else None
    if only:
        unknown = only - set(suites)
        if unknown:
            ap.error(
                f"unknown suite(s): {sorted(unknown)}; "
                f"valid suites: {', '.join(sorted(suites))}"
            )

    print("name,us_per_call,derived")
    failures = 0
    all_rows: list[dict] = []
    for name, fn in suites.items():
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            for row in fn():
                all_rows.append(dict(
                    name=row["name"],
                    us_per_call=round(float(row["us_per_call"]), 1),
                    derived=str(row["derived"]),
                ))
                d = str(row["derived"]).replace(",", ";")
                print(f"{row['name']},{row['us_per_call']:.1f},{d}", flush=True)
        except Exception as e:  # pragma: no cover
            failures += 1
            all_rows.append(dict(name=name, us_per_call=None,
                                 derived=f"ERROR {type(e).__name__}: {e}"))
            print(f"{name},ERROR,{type(e).__name__}: {e}", flush=True)
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)

    if args.json:
        ran = sorted(only) if only else sorted(suites)
        now = time.strftime("%Y-%m-%dT%H:%M:%S%z")
        suite_meta: dict = {}
        if merge and os.path.exists(args.json):
            # --suite: keep the recorded rows (and per-suite measurement
            # conditions) of suites NOT run this time.  Row names are
            # "<suite>/<case>"; suite-level ERROR rows are named bare
            # "<suite>".
            with open(args.json) as f:
                old = json.load(f)
            kept = [r for r in old.get("rows", [])
                    if r["name"].split("/")[0] not in only]
            all_rows = kept + all_rows
            suite_meta = {k: v for k, v in old.get("suite_meta", {}).items()
                          if k not in only}
        # quick/timestamp describe only THIS invocation; per-row
        # conditions live in suite_meta (rows can be merged across runs).
        for s in ran:
            suite_meta[s] = dict(quick=quick, timestamp=now)
        payload = dict(
            schema="bench_sort/v1",
            quick=quick,
            only=sorted(only) if only else None,
            timestamp=now,
            suite_meta=dict(sorted(suite_meta.items())),
            rows=all_rows,
        )
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"# wrote {len(all_rows)} rows to {args.json}", file=sys.stderr)

    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
