"""Control for `correct`: the program's truncated count in the timed path.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 \
        [--max-iters 256] [--seconds 5]

Runs the cell as `run.py` does, with `EngineConfig.max_iters` set on every
query of its traffic: the engine then stops each DFS after that many loop
trips and returns a partial count, an approximate answer where the
configuration promises an exact one. Prints each seed's compared numbers and,
as its last line, all of them as JSON. A sound comparison calls every one of
these runs not correct. Benchmark runs never run this; it needs a TPU.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import spec  # noqa: E402


def truncated(cell: spec.Cell, max_iters: int) -> spec.Cell:
    """`cell` with every query of its traffic capped at `max_iters` trips."""
    out = copy.deepcopy(cell)
    for q in out.traffic["queries"]:
        q["cfg"]["max_iters"] = max_iters
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--max-iters", type=int, default=256)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    cell = truncated(spec.load_cell(args.workload), args.max_iters)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"needs {cell.chips} TPU chips, JAX found {devices}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import harness

    readings = {}
    for seed in args.seeds:
        out = harness.run_cell(cell, seed, args.seconds, False,
                               devices[:cell.chips], time.perf_counter(),
                               log=lambda s: print(s, flush=True))
        readings[seed] = {"correct": out["correct"], **{
            k: c["value"] for k, c in out["checks"].items()}}
        print(f"control seed {seed}: {readings[seed]}", flush=True)
    print(json.dumps({"workload": args.workload, "max_iters": args.max_iters,
                      "seconds_total": time.perf_counter() - T_START,
                      "readings": readings}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
