"""Benchmark entry point: one run of one cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell, its configuration and its traffic
are read from BENCHMARK.json and the files it names. The run builds the
cell's graph from its configuration (whose generator seed is fixed), starts
`MCEService` on the cell's chips, warms up every query of the traffic
(set-up), then sends the traffic's queries back to back, from an offset into
the mix drawn from `--seed`, for `--seconds` (the window), and checks every
answer against a plain reference count. With `--trace 1` the first query of
the window is traced and the cell's per-layer metrics are reported in place
of the end-to-end ones.

It exits 2, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for. The last stdout line is the result as one JSON object; the
last stderr lines are the numbers compared, each beside its limit.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"{args.workload} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import harness

    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           devices[:cell.chips], T_START,
                           log=lambda s: print(s, flush=True))
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
