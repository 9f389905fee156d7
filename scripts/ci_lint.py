"""Dependency-free CI checks.

Default mode: line length + trailing whitespace over the Python tree.
``--docs`` mode (the Makefile `docs` target): README/docs internal-link
integrity + no stray __pycache__/*.pyc tracked in git.
``--bench`` mode (the Makefile `bench-perf` / `bench-interference` /
`bench-faults` targets): BENCH_sim.json exists and parses against its
schema (docs/performance.md); BENCH_interference.json — when present —
matches bench_interference/v1 or /v2 (docs/interference.md; v2 records
the topology per cell); BENCH_faults.json — when present — matches
bench_faults/v1 (docs/faults.md); BENCH_notifications.json — when
present — matches bench_notifications/v1 (docs/policy_api.md).
``--topology`` mode (`make lint` / bench-smoke): instantiates every
registered topology at small scale and runs the structural invariant
battery headlessly (docs/topology.md), including the fault-mask checks
under a seeded fault state (docs/faults.md) — needs numpy + src.
"""

import argparse
import json
import pathlib
import re
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent

_LINK_RE = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
_EXTERNAL = ("http://", "https://", "mailto:")


def lint_style() -> list:
    bad = []
    for root in ("src", "benchmarks", "examples"):
        for p in (ROOT / root).rglob("*.py"):
            if "__pycache__" in p.parts:
                continue
            for i, line in enumerate(p.read_text().splitlines(), 1):
                rel = p.relative_to(ROOT)
                if len(line) > 100:
                    bad.append(f"{rel}:{i}: line too long ({len(line)} > 100)")
                if re.search(r"[ \t]+$", line):
                    bad.append(f"{rel}:{i}: trailing whitespace")
    return bad


def lint_docs_links() -> list:
    """Every relative markdown link in README.md / docs/*.md resolves."""
    bad = []
    pages = [ROOT / "README.md"] + sorted((ROOT / "docs").glob("*.md"))
    for page in pages:
        if not page.exists():
            bad.append(f"{page.relative_to(ROOT)}: missing")
            continue
        for i, line in enumerate(page.read_text().splitlines(), 1):
            for target in _LINK_RE.findall(line):
                if target.startswith(_EXTERNAL) or target.startswith("#"):
                    continue
                path = target.split("#", 1)[0]
                if not path:
                    continue
                if not (page.parent / path).resolve().exists():
                    bad.append(f"{page.relative_to(ROOT)}:{i}: "
                               f"broken link -> {target}")
    return bad


def lint_tracked_pycache() -> list:
    """No __pycache__ dirs or *.pyc files committed to the repo."""
    try:
        out = subprocess.run(["git", "ls-files"], cwd=ROOT, check=True,
                             capture_output=True, text=True).stdout
    except (OSError, subprocess.CalledProcessError):
        return []  # not a git checkout (e.g. sdist) — nothing to check
    return [f"{f}: __pycache__/*.pyc tracked in git (add to .gitignore)"
            for f in out.splitlines()
            if "__pycache__" in f or f.endswith(".pyc")]


#: BENCH_sim.json contract (emitted by benchmarks/perf_sim.py): top-level
#: fields -> type, and per-backend numeric fields
_BENCH_SCHEMA_TOP = {"schema": str, "flows": int, "phases_timed": int,
                     "topology": dict, "seed_exact": bool,
                     "backends": dict, "speedup": dict}
_BENCH_BACKEND_FIELDS = ("phase_s", "phases_per_s", "flows_per_s")


def lint_bench_schema(require: bool = False) -> list:
    """BENCH_sim.json parses and matches bench_sim/v1 or /v2.

    v2 (benchmarks/perf_sim.py since the device-resident engine) adds a
    required numeric ``compile_s`` per backend — the one-time first-call
    cost split out of ``phase_s`` — and requires non-empty ``stages_s``
    for jax* backends (an empty dict there means the jitted pipeline
    silently fell back / never profiled)."""
    path = ROOT / "BENCH_sim.json"
    if not path.exists():
        return ["BENCH_sim.json: missing (run `make bench-perf`)"] \
            if require else []
    try:
        doc = json.loads(path.read_text())
    except json.JSONDecodeError as e:
        return [f"BENCH_sim.json: unparseable ({e})"]
    bad = []
    for key, typ in _BENCH_SCHEMA_TOP.items():
        if key not in doc:
            bad.append(f"BENCH_sim.json: missing key {key!r}")
        elif not isinstance(doc[key], typ):
            bad.append(f"BENCH_sim.json: {key!r} should be {typ.__name__}")
    schema = doc.get("schema")
    if schema not in (None, "bench_sim/v1", "bench_sim/v2"):
        bad.append(f"BENCH_sim.json: unknown schema {schema!r}")
    v2 = schema == "bench_sim/v2"
    fields = _BENCH_BACKEND_FIELDS + (("compile_s",) if v2 else ())
    for name, entry in (doc.get("backends") or {}).items():
        for f in fields:
            if not isinstance(entry.get(f), (int, float)):
                bad.append(f"BENCH_sim.json: backends.{name}.{f} "
                           f"missing or non-numeric")
        stages = entry.get("stages_s", {})
        if not isinstance(stages, dict):
            bad.append(f"BENCH_sim.json: backends.{name}.stages_s "
                       f"should be a dict")
        elif v2 and name.startswith("jax") and not stages:
            bad.append(f"BENCH_sim.json: backends.{name}.stages_s empty "
                       f"(jax arm must record stage timings)")
    for name, v in (doc.get("speedup") or {}).items():
        if not isinstance(v, (int, float)):
            bad.append(f"BENCH_sim.json: speedup.{name} non-numeric")
    return bad


#: BENCH_interference.json contract (benchmarks/interference_matrix.py):
#: top-level fields -> type, and per-cell numeric fields
_BENCH_INT_SCHEMA_TOP = {"schema": str, "rounds": int, "seed": int,
                         "topology": dict, "mixes": list, "policies": list,
                         "matrix": dict, "checks": dict}
_BENCH_INT_CELL_FIELDS = ("victim_slowdown", "victim_time_us",
                          "victim_alone_us", "victim_nonmin_fraction")


def lint_bench_interference_schema(require: bool = False) -> list:
    """BENCH_interference.json parses and matches bench_interference/v1."""
    path = ROOT / "BENCH_interference.json"
    if not path.exists():
        return ["BENCH_interference.json: missing "
                "(run `make bench-interference`)"] if require else []
    try:
        doc = json.loads(path.read_text())
    except json.JSONDecodeError as e:
        return [f"BENCH_interference.json: unparseable ({e})"]
    bad = []
    for key, typ in _BENCH_INT_SCHEMA_TOP.items():
        if key not in doc:
            bad.append(f"BENCH_interference.json: missing key {key!r}")
        elif not isinstance(doc[key], typ):
            bad.append(f"BENCH_interference.json: {key!r} should be "
                       f"{typ.__name__}")
    schema = doc.get("schema")
    if schema not in (None, "bench_interference/v1",
                      "bench_interference/v2"):
        bad.append(f"BENCH_interference.json: unknown schema {schema!r}")
    # v2: every cell must say which topology it ran on
    want_topology = schema == "bench_interference/v2"
    for mix, row in (doc.get("matrix") or {}).items():
        for policy in (doc.get("policies") or list(row)):
            cell = row.get(policy)
            if not isinstance(cell, dict):
                bad.append(f"BENCH_interference.json: matrix.{mix} missing "
                           f"policy {policy!r}")
                continue
            for f in _BENCH_INT_CELL_FIELDS:
                if not isinstance(cell.get(f), (int, float)):
                    bad.append(f"BENCH_interference.json: matrix.{mix}."
                               f"{policy}.{f} missing or non-numeric")
            if want_topology and not isinstance(cell.get("topology"), str):
                bad.append(f"BENCH_interference.json: matrix.{mix}."
                           f"{policy}.topology missing or not a string "
                           f"(required by {schema})")
            if not isinstance(cell.get("aggressor_slowdowns", {}), dict):
                bad.append(f"BENCH_interference.json: matrix.{mix}."
                           f"{policy}.aggressor_slowdowns should be a dict")
    return bad


#: BENCH_faults.json contract (benchmarks/fault_matrix.py): top-level
#: fields -> type, and per-cell numeric fields (docs/faults.md)
_BENCH_FAULTS_SCHEMA_TOP = {"schema": str, "rounds": int, "seed": int,
                            "topologies": list, "scenarios": dict,
                            "policies": list, "matrix": dict,
                            "checks": dict}
_BENCH_FAULTS_CELL_FIELDS = ("victim_slowdown", "victim_time_us",
                             "victim_alone_us", "victim_recovery_rounds",
                             "victim_recovery_time_us", "stranded_flows")


def lint_bench_faults_schema(require: bool = False) -> list:
    """BENCH_faults.json parses and matches bench_faults/v1."""
    path = ROOT / "BENCH_faults.json"
    if not path.exists():
        return ["BENCH_faults.json: missing (run `make bench-faults`)"] \
            if require else []
    try:
        doc = json.loads(path.read_text())
    except json.JSONDecodeError as e:
        return [f"BENCH_faults.json: unparseable ({e})"]
    bad = []
    for key, typ in _BENCH_FAULTS_SCHEMA_TOP.items():
        if key not in doc:
            bad.append(f"BENCH_faults.json: missing key {key!r}")
        elif not isinstance(doc[key], typ):
            bad.append(f"BENCH_faults.json: {key!r} should be "
                       f"{typ.__name__}")
    if doc.get("schema") not in (None, "bench_faults/v1"):
        bad.append(f"BENCH_faults.json: unknown schema "
                   f"{doc.get('schema')!r}")
    for cellkey, row in (doc.get("matrix") or {}).items():
        for policy in (doc.get("policies") or list(row)):
            cell = row.get(policy)
            if not isinstance(cell, dict):
                bad.append(f"BENCH_faults.json: matrix.{cellkey} missing "
                           f"policy {policy!r}")
                continue
            for f in _BENCH_FAULTS_CELL_FIELDS:
                if not isinstance(cell.get(f), (int, float)):
                    bad.append(f"BENCH_faults.json: matrix.{cellkey}."
                               f"{policy}.{f} missing or non-numeric")
            if not isinstance(cell.get("topology"), str):
                bad.append(f"BENCH_faults.json: matrix.{cellkey}."
                           f"{policy}.topology missing or not a string")
            if not isinstance(cell.get("scenario"), str):
                bad.append(f"BENCH_faults.json: matrix.{cellkey}."
                           f"{policy}.scenario missing or not a string")
            if not isinstance(cell.get("tenant_recovery", {}), dict):
                bad.append(f"BENCH_faults.json: matrix.{cellkey}."
                           f"{policy}.tenant_recovery should be a dict")
    return bad


#: BENCH_notifications.json contract (benchmarks/notification_matrix.py):
#: top-level fields -> type, per-tenancy-cell and per-workload-arm
#: numeric fields (docs/policy_api.md)
_BENCH_NOTIF_SCHEMA_TOP = {"schema": str, "rounds": int, "seed": int,
                           "topology": str, "notify_params": dict,
                           "policies": list, "workloads": dict,
                           "matrix": dict, "checks": dict}
_BENCH_NOTIF_CELL_FIELDS = ("victim_slowdown", "victim_time_us",
                            "victim_alone_us", "notification_events")


def lint_bench_notifications_schema(require: bool = False) -> list:
    """BENCH_notifications.json parses, matches bench_notifications/v1."""
    path = ROOT / "BENCH_notifications.json"
    if not path.exists():
        return ["BENCH_notifications.json: missing "
                "(run `make bench-notifications`)"] if require else []
    try:
        doc = json.loads(path.read_text())
    except json.JSONDecodeError as e:
        return [f"BENCH_notifications.json: unparseable ({e})"]
    bad = []
    for key, typ in _BENCH_NOTIF_SCHEMA_TOP.items():
        if key not in doc:
            bad.append(f"BENCH_notifications.json: missing key {key!r}")
        elif not isinstance(doc[key], typ):
            bad.append(f"BENCH_notifications.json: {key!r} should be "
                       f"{typ.__name__}")
    if doc.get("schema") not in (None, "bench_notifications/v1"):
        bad.append(f"BENCH_notifications.json: unknown schema "
                   f"{doc.get('schema')!r}")
    for mix, row in (doc.get("matrix") or {}).items():
        for policy in (doc.get("policies") or list(row)):
            cell = row.get(policy)
            if not isinstance(cell, dict):
                bad.append(f"BENCH_notifications.json: matrix.{mix} "
                           f"missing policy {policy!r}")
                continue
            for f in _BENCH_NOTIF_CELL_FIELDS:
                if not isinstance(cell.get(f), (int, float)):
                    bad.append(f"BENCH_notifications.json: matrix.{mix}."
                               f"{policy}.{f} missing or non-numeric")
    for name, cell in (doc.get("workloads") or {}).items():
        arms = cell.get("arms") if isinstance(cell, dict) else None
        if not isinstance(arms, dict):
            bad.append(f"BENCH_notifications.json: workloads.{name}.arms "
                       f"should be a dict")
            continue
        for policy in (doc.get("policies") or list(arms)):
            arm = arms.get(policy)
            if not isinstance(arm, dict) \
                    or not isinstance(arm.get("median_us"), (int, float)):
                bad.append(f"BENCH_notifications.json: workloads.{name}."
                           f"arms.{policy}.median_us missing or "
                           f"non-numeric")
    checks = doc.get("checks") or {}
    if not isinstance(checks.get("wins_with_events_cells", []), list):
        bad.append("BENCH_notifications.json: checks."
                   "wins_with_events_cells should be a list")
    return bad


def lint_topology_invariants() -> list:
    """Every registered topology passes the invariant battery at its
    small scale (repro.dragonfly.invariants.check_all), plus the
    fault-mask battery under a deterministic seeded fault state
    (docs/faults.md)."""
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import numpy as np

        from repro.dragonfly.invariants import (InvariantViolation,
                                                check_all,
                                                check_capacity_scale,
                                                check_fault_mask,
                                                sample_pairs)
        from repro.dragonfly.topology import (registered_topologies,
                                              small_topology)
        from repro.faults import (FaultSchedule, link_degrade, link_down,
                                  router_down)
    except ImportError as e:
        return [f"--topology: cannot import repro.dragonfly ({e})"]
    bad = []
    for name in registered_topologies():
        try:
            topo = small_topology(name)
            check_all(topo, n_pairs=128)
            # deterministic fault state: 2 random global links down, one
            # more degraded, router 0 down — then the mask battery
            sched = FaultSchedule.of(
                link_down(n_random=2, seed=11),
                link_degrade(0.25, n_random=1, seed=12),
                router_down([0])).bind(topo)
            state = sched.state_at(0)
            check_capacity_scale(topo, state)
            src, dst = sample_pairs(topo, n=64, seed=2)
            check_fault_mask(topo, state.dead, src, dst,
                             rng=np.random.default_rng(8))
            check_fault_mask(topo, np.zeros(topo.n_links, dtype=bool),
                             src, dst, rng=np.random.default_rng(8))
        except InvariantViolation as e:
            bad.append(f"topology {name!r}: {e}")
        except Exception as e:  # construction/battery crash
            bad.append(f"topology {name!r}: {type(e).__name__}: {e}")
        else:
            print(f"# topology {name}: ok ({topo.spec_str()})",
                  file=sys.stderr)
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--docs", action="store_true",
                    help="check README/docs links and tracked "
                         "__pycache__ instead of Python style")
    ap.add_argument("--bench", action="store_true",
                    help="require BENCH_sim.json and check its schema")
    ap.add_argument("--topology", action="store_true",
                    help="run the topology-family invariant battery on "
                         "every registered topology at small scale")
    args = ap.parse_args(argv)
    if args.topology:
        bad = lint_topology_invariants()
    elif args.bench:
        bad = (lint_bench_schema(require=True)
               + lint_bench_interference_schema()
               + lint_bench_faults_schema()
               + lint_bench_notifications_schema())
    elif args.docs:
        bad = (lint_docs_links() + lint_tracked_pycache()
               + lint_bench_schema()
               + lint_bench_interference_schema()
               + lint_bench_faults_schema()
               + lint_bench_notifications_schema())
    else:
        bad = lint_style()
    print("\n".join(bad))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
