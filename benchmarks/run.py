"""Benchmark aggregator: one section per paper table/figure + roofline.

``python -m benchmarks.run [--fast]``
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller graph suite (CI)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset (fig7,fig8,...)")
    args = ap.parse_args()

    from benchmarks import (fig7_speedups, fig8_reduction, fig9_calls,
                            fig10_forbidden, fig11_visits, table3_ablation,
                            roofline)

    sections = [
        ("fig8", fig8_reduction.main),
        ("fig9", fig9_calls.main),
        ("fig10", fig10_forbidden.main),
        ("fig11", fig11_visits.main),
        ("fig7", fig7_speedups.main),
        ("table3", table3_ablation.main),
        ("roofline", roofline.main),
    ]
    only = set(args.only.split(",")) if args.only else None
    failed = []
    for name, fn in sections:
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            out = fn(fast=args.fast)
        except Exception as e:  # keep the suite running; fail at the end
            out = f"# {name} FAILED: {type(e).__name__}: {e}\n"
            failed.append(name)
        sys.stdout.write(f"\n===== {name} ({time.time()-t0:.1f}s) =====\n")
        sys.stdout.write(out)
        sys.stdout.flush()
    if failed:
        print(f"failed sections: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
