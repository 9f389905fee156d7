"""Readings that set a cell's limits: the program's numbers over many
seeds and the bfloat16 control's, at the cell's own size, in one process.

    python3 bench/readings.py --workload <cell> --seeds 201-212 \
        --control 201-203 --free 201-202 --units 4

Each seed builds the cell as ``run.py`` does, warms up, runs ``--units``
units of the window's loop, and compares the sampled phases with the
float64 reference; seeds in ``--control`` also compute the control.
Seeds in ``--free`` sample every phase and also compare it with a
free-running reference (``check.compare(free=True)``), which never takes
the program's carried state; their lines give its worst numbers and the
worst ``t_rel_err`` of each phase.  One JSON line per seed, then the
largest program reading and the smallest control reading of every
number.  The benchmark's own runs do not run this.
"""

import argparse
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def _seeds(text: str) -> list:
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def every_phase(i: int) -> bool:
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--free", default="")
    ap.add_argument("--units", type=int, required=True)
    args = ap.parse_args(argv)

    import jax

    from bench import check, harness, traffic
    if jax.devices()[0].platform != "tpu":
        print("readings: needs a TPU", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    spec = harness.cell_spec(args.workload)
    mix, cfg, cell = spec["mix"], spec["config"], spec["cell"]
    control = set(_seeds(args.control)) if args.control else set()
    free = set(_seeds(args.free)) if args.free else set()
    params = dict(cfg["sim"], **cfg["program"])
    worst: dict = {}
    least: dict = {}
    for seed in _seeds(args.seeds):
        t0 = time.perf_counter()
        first, sampled = harness.sampler(mix, seed)
        if seed in free:
            sampled = every_phase
        driver = traffic.Driver(mix, cfg, seed, cell.get("plan_pairs"),
                                params, sampled)
        for _ in range(mix["warmup_units"]):
            driver.step()
        first[0] = driver.phases_run
        for _ in range(args.units):
            driver.step()
        res = check.compare(driver, control=seed in control)
        line = {}
        if seed in free:
            f = check.compare(driver, free=True)
            line = {"free": f["program"], "free_by_phase": f["by_phase"],
                    "resynced_by_phase": res["by_phase"]}
        for k, v in res["program"].items():
            worst[k] = max(worst.get(k, 0.0), v)
        for k, v in (res["control"] or {}).items():
            least[k] = min(least.get(k, float("inf")), v)
        print(json.dumps({"seed": seed, "phases": res["phases"],
                          "program": res["program"],
                          "control": res["control"], **line,
                          "seconds": time.perf_counter() - t0}), flush=True)
        del driver, res, line
        gc.collect()
    print(json.dumps({"workload": args.workload, "lower": worst,
                      "upper": least}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
