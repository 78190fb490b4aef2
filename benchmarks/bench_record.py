"""Benchmark trajectory records: append, don't overwrite, BENCH_*.json.

The perf benchmarks used to `json.dump` a single snapshot, so every CI run
erased the previous one and the "trajectory" was always one point. Each
BENCH_<name>.json now keeps the latest run's metrics at top level (compat:
consumers keep reading e.g. doc["speedup"]) plus the full history under a
"runs" key — a list of {commit, date, **metrics} records, one appended per
benchmark invocation. The commit comes from the CI env (GITHUB_SHA) with a
`git rev-parse` fallback; pre-trajectory files (no "runs" key) are migrated
in place, their old top-level metrics becoming the first record.

Record dates resolve CI pipeline date -> the commit's own `git show`
date -> wall clock (re-runs outside CI used to stamp "unknown");
`--migrate-dates` backfills old "unknown" records in place.

Validate (exit 1 + reasons on stderr for malformed files):

  PYTHONPATH=src python -m benchmarks.bench_record --validate BENCH_*.json \
      [--require KEY ...] [--migrate-dates]

The mce-smoke CI job runs this over every emitted BENCH file, so a
benchmark that regresses to snapshot-overwriting fails the build;
`--require` additionally pins the metric fields a benchmark is
contracted to emit (e.g. the stream workload's perbucket_idle/steals).
"""
from __future__ import annotations

import argparse
import datetime
import json
import os
import subprocess
import sys
from typing import List

RESERVED = ("runs", "commit", "date")


def _commit() -> str:
    for var in ("GITHUB_SHA", "CI_COMMIT_SHA"):
        sha = os.environ.get(var)
        if sha:
            return sha
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10, check=True).stdout.strip()
    except Exception:
        return "unknown"


def _commit_date(sha: str) -> str:
    """Committer date (ISO 8601) of `sha`, or 'unknown' off-repo."""
    if not sha or sha == "unknown":
        return "unknown"
    try:
        out = subprocess.run(
            ["git", "show", "-s", "--format=%cI", sha],
            capture_output=True, text=True, timeout=10, check=True
        ).stdout.strip()
        return out or "unknown"
    except Exception:
        return "unknown"


def _date(commit: str) -> str:
    """Record timestamp: CI pipeline date, else the commit's own date,
    else wall clock. Benchmarks re-run against an old checkout used to
    stamp 'unknown' (the CI env vars were the only source); the commit
    date keeps the trajectory orderable everywhere git is available."""
    for var in ("BENCH_DATE", "CI_PIPELINE_CREATED_AT"):
        d = os.environ.get(var)
        if d:
            return d
    d = _commit_date(commit)
    if d != "unknown":
        return d
    return (datetime.datetime.now(datetime.timezone.utc)
            .isoformat(timespec="seconds"))


def migrate_dates(path: str) -> int:
    """Backfill 'unknown' run dates in place from each record's commit date.

    Returns how many records were fixed. Records whose commit is itself
    unknown (or unresolvable in this clone) are left as-is."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return 0
    if not isinstance(doc, dict) or not isinstance(doc.get("runs"), list):
        return 0
    fixed = 0
    for rec in doc["runs"]:
        if isinstance(rec, dict) and rec.get("date") == "unknown":
            d = _commit_date(rec.get("commit", "unknown"))
            if d != "unknown":
                rec["date"] = d
                fixed += 1
    if fixed:
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
    return fixed


def append_run(path: str, metrics: dict) -> dict:
    """Append one run record to `path`; returns the document written.

    Document shape: {**metrics, "runs": [...older records, new record]}
    with record = {"commit": ..., "date": ..., **metrics}. An existing file
    in the legacy single-snapshot schema (no "runs") contributes its
    top-level metrics as the first record.
    """
    for k in RESERVED:
        if k in metrics:
            raise ValueError(f"metric name {k!r} is reserved")
    runs: List[dict] = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                old = json.load(f)
        except (OSError, ValueError):
            old = None            # unreadable snapshot: start fresh
        if isinstance(old, dict):
            if isinstance(old.get("runs"), list):
                runs = old["runs"]
            elif old:             # legacy snapshot -> first record
                runs = [dict(old, commit="unknown", date="unknown")]
    commit = _commit()
    record = dict(commit=commit, date=_date(commit), **metrics)
    doc = dict(metrics)
    doc["runs"] = runs + [record]
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    return doc


def validate(path: str) -> List[str]:
    """Schema check for one BENCH file; returns problems (empty = valid)."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return [f"{path}: unreadable ({e})"]
    if not isinstance(doc, dict):
        return [f"{path}: top level is not an object"]
    runs = doc.get("runs")
    if not isinstance(runs, list) or not runs:
        return [f"{path}: missing or empty 'runs' list "
                "(snapshot-overwrite regression?)"]
    problems = []
    for i, rec in enumerate(runs):
        if not isinstance(rec, dict):
            problems.append(f"{path}: runs[{i}] is not an object")
            continue
        for key in ("commit", "date"):
            if not isinstance(rec.get(key), str):
                problems.append(f"{path}: runs[{i}] missing string {key!r}")
    last = runs[-1]
    if isinstance(last, dict):
        for k, v in last.items():
            if k in ("commit", "date"):
                continue
            if k not in doc:
                problems.append(f"{path}: last-run metric {k!r} not "
                                "mirrored at top level")
            elif doc[k] != v:
                problems.append(f"{path}: top-level {k!r} differs from the "
                                "last run record")
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--validate", nargs="+", metavar="FILE", required=True,
                    help="BENCH json files to schema-check")
    ap.add_argument("--require", nargs="*", metavar="KEY", default=[],
                    help="metric keys that must exist at top level of "
                         "every validated file (CI pins the fields a "
                         "benchmark is contracted to emit)")
    ap.add_argument("--migrate-dates", action="store_true",
                    help="backfill 'unknown' run dates in place from each "
                         "record's commit date before validating")
    args = ap.parse_args(argv)
    problems = []
    for path in args.validate:
        if args.migrate_dates:
            n = migrate_dates(path)
            if n:
                print(f"{path}: backfilled {n} run date(s)")
        problems += validate(path)
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            doc = {}
        for key in args.require:
            if not isinstance(doc, dict) or key not in doc:
                problems.append(f"{path}: required metric {key!r} missing "
                                "at top level")
    for msg in problems:
        print(msg, file=sys.stderr)
    if not problems:
        print(f"ok: {len(args.validate)} BENCH file(s) valid")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
