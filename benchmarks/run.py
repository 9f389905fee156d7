"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  --full runs paper-scale sweeps
(minutes); the default is a reduced pass suitable for CI."""

from __future__ import annotations

import argparse
import sys
import time

from repro.compat import enable_compile_cache


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset, e.g. fig3,fig7")
    ap.add_argument("--policy", default="app_aware",
                    choices=("static", "app_aware", "eps_greedy"),
                    help="adaptive arm for the policy-driven suites "
                         "(fig8, fig10): which repro.policy engine to run "
                         "against the static Default/HIGH-BIAS arms")
    ap.add_argument("--topology", default=None,
                    help="make_topology spec swapping the machine for the "
                         "topology-aware suites (fig7, fig8, fig10, "
                         "interference), e.g. 'dragonfly_plus:p=4,"
                         "a_leaf=8,a_spine=8,h=2,g=17' (docs/topology.md)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    from benchmarks import (fig3_allocation, fig4_fig5_hostnoise,
                            fig7_routing_pingpong, fig8_microbench,
                            fig10_applications, interference_matrix,
                            model_validation, perf_sim,
                            table1_correlation, tpu_selector)
    suites = {
        "fig3": fig3_allocation.main,
        "table1": table1_correlation.main,
        "fig4fig5": fig4_fig5_hostnoise.main,
        "fig7": fig7_routing_pingpong.main,
        "fig8": fig8_microbench.main,
        "fig10": fig10_applications.main,
        "model": model_validation.main,
        "tpu": tpu_selector.main,
        "perf": perf_sim.main,
        "interference": interference_matrix.main,
    }
    #: suites whose adaptive arm is a pluggable repro.policy engine
    policy_suites = {"fig8", "fig10"}
    #: suites that accept the --topology machine swap
    topology_suites = {"fig7", "fig8", "fig10", "interference"}
    chosen = (args.only.split(",") if args.only else list(suites))
    print("name,us_per_call,derived")
    for key in chosen:
        t0 = time.time()
        kw = {"policy": args.policy} if key in policy_suites else {}
        if key in topology_suites and args.topology:
            kw["topology"] = args.topology
        suites[key](full=args.full, **kw)
        print(f"# {key} done in {time.time() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
