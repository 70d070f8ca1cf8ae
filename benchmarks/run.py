"""Benchmark driver — one function per paper table/figure.
Prints ``name,us_per_call,...`` CSV per benchmark; ``--json PATH``
additionally writes the structured rows (suite -> [row dicts]) so
``BENCH_*.json`` trajectory files can accumulate across PRs.  Writing
MERGES by row name into the existing file: rows this run re-measured
are replaced in place, rows it did not produce (e.g. the normal
representation rows during a ``BENCH_SHARDS_ONLY=1`` sharded append,
or the sharded rows during a normal run) are preserved — a partial
run never drops the rest of the trajectory.

``--compare BASELINE.json`` diffs this run's per-row timing columns
against a checked-in trajectory file (loaded BEFORE ``--json``
overwrites it) and exits non-zero when any ``digraph`` row regresses by
more than ``REGRESSION_FACTOR`` — the smoke-gate guard for the paper's
headline representation.

Usage: PYTHONPATH=src python -m benchmarks.run \
    [--only load|clone|update|traversal|stream|alloc|recovery|serve] \
    [--json PATH] [--compare BASELINE.json]
"""
from __future__ import annotations

import argparse
import json
import sys
import time

#: A gated row slower than baseline by more than this fails --compare.
REGRESSION_FACTOR = 1.3
#: Row columns holding the comparable per-row timing (first match wins).
_TIME_KEYS = ("us_per_call", "us_per_round", "ms_per_call")
#: Representation rows that gate the traversal/stream suites.  Elsewhere
#: only the paper's headline ``digraph`` rows gate — the other reps'
#: update/load costs are the measured result, not an invariant, but on
#: the walk suites every representation rides the same image engine, so
#: a regression in any of them is an engine regression.
GATED_REPS = ("digraph", "coo", "lazy", "chunked", "vector2d")
FULLY_GATED_SUITES = ("traversal", "stream")


def _row_time(row: dict):
    for k in _TIME_KEYS:
        if k in row:
            try:
                return float(row[k])
            except (TypeError, ValueError):
                return None
    return None


def compare_results(
    results: dict, baseline: dict, *, factor: float = REGRESSION_FACTOR
) -> list[str]:
    """Diff per-row timings vs a baseline; return regression messages.

    Rows are matched by their ``name`` field across all suites present
    in BOTH runs.  On the traversal and stream suites every one of the
    five representations' rows gates the run (all five ride the shared
    walk-image engine); on the other suites only the rows whose
    representation component (the last ``/``-separated token) is exactly
    ``digraph`` gate — the comparison ratios of the other
    representations there are the measured result, not an invariant.
    ``digraph_flat`` is the seed baseline row, never gated.
    """
    base_rows = {
        r["name"]: r
        for rows in baseline.values()
        if isinstance(rows, list)
        for r in rows
        if isinstance(r, dict) and "name" in r
    }
    failures: list[str] = []
    for suite, rows in results.items():
        for row in rows:
            name = row.get("name")
            old = base_rows.get(name)
            if old is None:
                continue
            t_new, t_old = _row_time(row), _row_time(old)
            if t_new is None or t_old is None or t_old <= 0:
                continue
            ratio = t_new / t_old
            rep = name.rsplit("/", 1)[-1]
            gate = rep == "digraph" or (
                suite in FULLY_GATED_SUITES and rep in GATED_REPS
            )
            tag = "FAIL" if gate and ratio > factor else "ok"
            print(
                f"# compare {tag}: {name} {t_old:.1f} -> {t_new:.1f} "
                f"({ratio:.2f}x)",
                file=sys.stderr,
            )
            if gate and ratio > factor:
                failures.append(
                    f"{name}: {t_old:.1f} -> {t_new:.1f} ({ratio:.2f}x > "
                    f"{factor}x)"
                )
    return failures


def merge_results(prev: dict, new: dict) -> dict:
    """Merge this run's rows into an existing trajectory file by name.

    Suites absent from ``new`` pass through untouched; within a suite
    present in both, rows keep the existing file's order, re-measured
    rows (matched on ``name``) are replaced in place, and rows new to
    this run append at the end.
    """
    out = dict(prev)
    for suite, rows in new.items():
        old = out.get(suite)
        if not isinstance(old, list):
            out[suite] = rows
            continue
        index = {
            r.get("name"): i
            for i, r in enumerate(old)
            if isinstance(r, dict) and "name" in r
        }
        merged = list(old)
        for r in rows:
            i = index.get(r.get("name") if isinstance(r, dict) else None)
            if i is None:
                merged.append(r)
            else:
                merged[i] = r
        out[suite] = merged
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument(
        "--json", default=None, metavar="PATH",
        help="also write results as JSON: {suite: [row, ...]}",
    )
    ap.add_argument(
        "--compare", default=None, metavar="BASELINE",
        help="diff per-row timings against a BENCH_*.json baseline and "
        f"fail on >{REGRESSION_FACTOR}x regression of any digraph row",
    )
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    from . import (
        bench_alloc,
        bench_clone,
        bench_load,
        bench_recovery,
        bench_serve,
        bench_stream,
        bench_traversal,
        bench_update,
    )

    suites = {
        "load": bench_load.run,          # paper Fig. 2 / Table 1
        "clone": bench_clone.run,        # paper Fig. 3
        "update": bench_update.run,      # paper Figs. 5-8
        "traversal": bench_traversal.run,  # paper Figs. 9-10
        "stream": bench_stream.run,      # paper Figs. 9-10, interleaved
        "alloc": bench_alloc.run,        # paper Fig. 11
        "recovery": bench_recovery.run,  # durability pipeline (§13)
        "serve": bench_serve.run,        # multi-tenant serving (§16)
    }
    if args.only and args.only not in suites:
        ap.error(f"unknown suite {args.only!r}; choose from {sorted(suites)}")
    if args.json:
        # fail fast on an unwritable --json path before burning suite time,
        # without truncating an existing trajectory file mid-failure
        with open(args.json, "a"):
            pass
    baseline = None
    if args.compare:
        # load the baseline up front: --json may overwrite the same file.
        # A missing/empty baseline (fresh checkout — note the --json
        # writability touch above may have just created a 0-byte file)
        # skips the gate instead of crashing: the first run seeds it.
        try:
            with open(args.compare) as fh:
                baseline = json.load(fh)
        except (FileNotFoundError, json.JSONDecodeError):
            print(
                f"# no usable baseline at {args.compare}; skipping compare",
                file=sys.stderr,
            )

    t0 = time.time()
    results: dict[str, list] = {}
    for name, fn in suites.items():
        if args.only and name != args.only:
            continue
        print(f"# === {name} ===", flush=True)
        results[name] = fn()
    print(f"# total {time.time()-t0:.1f}s", file=sys.stderr)
    # compare BEFORE --json may overwrite the same file: a failed gate
    # must leave the checked-in baseline intact, or the next run would
    # silently ratchet the regression in by comparing against it.
    failures: list[str] = []
    if baseline is not None:
        failures = compare_results(results, baseline)
    if args.json:
        if failures:
            print(
                f"# regression: NOT updating {args.json}", file=sys.stderr
            )
        else:
            try:
                with open(args.json) as fh:
                    prev = json.load(fh)
                if not isinstance(prev, dict):
                    prev = {}
            except (FileNotFoundError, json.JSONDecodeError):
                prev = {}  # fresh (or 0-byte touched) file: nothing to keep
            with open(args.json, "w") as fh:
                json.dump(merge_results(prev, results), fh, indent=1,
                          default=str)
            print(f"# wrote {args.json} (merged by row name)",
                  file=sys.stderr)
    if baseline is not None:
        if failures:
            print(
                "# REGRESSION vs " + args.compare + ":\n#   "
                + "\n#   ".join(failures),
                file=sys.stderr,
            )
            sys.exit(1)
        print(f"# compare vs {args.compare}: ok", file=sys.stderr)


if __name__ == "__main__":
    main()
