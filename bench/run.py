"""The benchmark's one command: one run of one cell on the chips it asks for.

    python3 bench/run.py --workload CELL --seed N --seconds S --trace 0|1

It builds the cell's machine and traffic from the seed, warms up every
shape the window uses, drives the jax phase engine for ``--seconds``,
checks the window's phases against the plain reference, and prints one
JSON line last on standard output.  With ``--trace 0`` the line carries
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics
from a profiled window.  Without a TPU, or with fewer chips than the
cell asks for, it exits 2 and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    spec = harness.cell_spec(args.workload)
    try:
        out = harness.run_cell(spec, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
