"""Benchmark harness — one function per paper table/figure + real host
microbenchmarks + the roofline summary of completed dry-runs.

Prints ``name,us_per_call,derived`` CSV (one row per measurement).

CLI (used by the CI ``bench-smoke`` job):
  --only a,b   run only the named microbench functions (skips paper tables
               and the roofline summary)
  --json PATH  also write {"rows": [row objects], "errors": [strings]}

Exits nonzero if any benchmark raised (timings never fail).
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# repo root, so `from benchmarks import ...` works when run as a script
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def ttft_percentiles(requests) -> dict:
    """Time-to-first-token percentiles (seconds) from the engine's
    ``record_times`` stamps.  Thin wrapper kept for callers of the historic
    location — the one shared implementation lives in ``repro.obs``
    (``obs/metrics.py``), next to the registry's histogram percentiles."""
    from repro.obs import ttft_percentiles as _ttft

    return _ttft(requests)


def _emit(rows, name, us, derived):
    us_s = "nan" if (isinstance(us, float) and math.isnan(us)) else f"{us:.1f}"
    print(f"{name},{us_s},{derived}")
    rows.append({"name": name, "us_per_call": None if us_s == "nan" else float(us),
                 "derived": derived})


def roofline_summary():
    """Summarize any dry-run JSONs already produced (experiments/dryrun/)."""
    import glob

    pat = os.path.join(os.path.dirname(__file__), "..", "experiments", "dryrun", "*.json")
    for path in sorted(glob.glob(pat)):
        with open(path) as f:
            r = json.load(f)
        name = f"roofline/{r['arch']}/{r['shape']}/{r['mesh']}"
        if r.get("hmp_sequence_parallel") is False:
            name += "/tp_only"
        yield (
            name,
            r["roofline_step_s"] * 1e6,
            f"bottleneck={r['bottleneck']},mfu={r['roofline_mfu']:.3f},"
            f"useful={r['useful_flops_ratio']:.2f}",
        )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default="",
                    help="comma-separated microbench function names")
    ap.add_argument("--json", default="", help="write rows as JSON to this path")
    args = ap.parse_args(argv)

    from benchmarks import microbench, paper_tables

    only = {n for n in args.only.split(",") if n}
    unknown = only - {fn.__name__ for fn in microbench.ALL}
    if unknown:
        ap.error(f"unknown microbench name(s): {sorted(unknown)}")

    rows: list = []
    errors: list = []
    print("name,us_per_call,derived")
    if not only:
        for fn in paper_tables.ALL:
            for row in fn():
                _emit(rows, *row)
    for fn in microbench.ALL:
        if only and fn.__name__ not in only:
            continue
        try:
            for row in fn():
                _emit(rows, *row)
        except Exception as e:  # noqa: BLE001 — record, run the rest, exit 1
            errors.append(f"{fn.__name__}: {type(e).__name__}: {e}")
            _emit(rows, f"micro/{fn.__name__}", float("nan"),
                  f"error:{type(e).__name__}")
    if not only:
        for row in roofline_summary():
            _emit(rows, *row)

    if args.json:
        with open(args.json, "w") as f:
            json.dump({"rows": rows, "errors": errors}, f, indent=2)
    if errors:
        print(f"{len(errors)} benchmark(s) raised:", file=sys.stderr)
        for e in errors:
            print(f"  {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
