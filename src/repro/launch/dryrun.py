import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run — deliverable (e).

For every (architecture x input shape) cell, lower + compile the
production step function (train_step / prefill / decode serve_step) on the
16x16 single-pod mesh and the 2x16x16 multi-pod mesh, print
memory_analysis / cost_analysis, and derive the roofline terms from the
compiled HLO (analysis/).  The XLA_FLAGS line above MUST precede any other
import (jax locks the device count at first init).

Usage:
    python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--out report.jsonl]
"""

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp

from repro.analysis.hlo_parse import parse_hlo
from repro.analysis.roofline import model_flops_estimate, roofline_terms
from repro.configs import (SHAPES, ShapeNotSupported, get_config,
                           input_specs)
from repro.launch.mesh import make_production_mesh
from repro.models import registry as model_registry
from repro.models.common import Family
from repro.sharding.partition import (decode_state_specs, default_policy,
                                      input_specs_sharding, param_specs)
from repro.train.optimizer import adamw_init
from repro.train.train_step import TrainConfig, train_step


def _sds(tree):
    """eval_shape pytree -> ShapeDtypeStruct pytree (already is)."""
    return tree


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               policy_overrides: dict | None = None,
               mesh_override: tuple | None = None,
               microbatch_override: int | None = None):
    """Lower + compile one (arch x shape x mesh) cell.

    Returns (report_dict, compiled) — compiled exposed for perf iteration.
    mesh_override: ((shape...), (axis names...)) — §Perf alternative
    parallelism splits of the same 256/512 chips.
    """
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    specs = input_specs(cfg, shape)          # raises ShapeNotSupported
    if mesh_override is not None:
        from repro.launch.mesh import make_mesh_for
        mesh = make_mesh_for(*mesh_override)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    policy = default_policy(mesh)
    # big dense models cannot hold fp32 master+Adam state in TP-only
    # shards: enable FSDP (ZeRO-3-style "d"-dim sharding over dp) when the
    # per-chip optimizer footprint would exceed ~5 GB
    from repro.analysis.roofline import param_counts_analytic
    total_params, _ = param_counts_analytic(cfg)
    tp = mesh.shape[policy.tp_axis]
    if shape.kind == "train" and total_params * 12.0 / tp > 1.5e9:
        from dataclasses import replace as _replace
        policy = _replace(policy, fsdp=True)
    if policy_overrides:
        from dataclasses import replace
        policy = replace(policy, **policy_overrides)

    params_sds = jax.eval_shape(
        lambda: model_registry.init_params(cfg, 0))
    p_shard = param_specs(params_sds, cfg, mesh, policy)
    in_shard = input_specs_sharding(specs, cfg, mesh, policy)

    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            from repro.train.train_step import auto_microbatch
            dp = 1
            for a in policy.dp_axes:
                dp *= mesh.shape[a]
            mb = auto_microbatch(cfg, shape.global_batch, shape.seq_len, dp)
            if microbatch_override is not None:
                mb = microbatch_override
            tcfg = TrainConfig(microbatch=mb)
            opt_sds = jax.eval_shape(adamw_init, params_sds)
            opt_shard = jax.tree_util.tree_map(
                lambda _: None, opt_sds)  # placeholder, built below
            from jax.sharding import NamedSharding, PartitionSpec as P
            scalar = NamedSharding(mesh, P())
            import repro.train.optimizer as _opt
            opt_shard = _opt.AdamWState(
                step=scalar, m=p_shard,
                v=jax.tree_util.tree_map(lambda s: s, p_shard))

            def fn(params, opt_state, batch):
                return train_step(params, opt_state, batch, cfg=cfg,
                                  tcfg=tcfg)

            lowered = jax.jit(
                fn,
                in_shardings=(p_shard, opt_shard, in_shard),
                out_shardings=(p_shard, opt_shard, None),
            ).lower(params_sds, opt_sds, specs)
        elif shape.kind == "prefill":
            state_sds = jax.eval_shape(
                lambda: model_registry.make_decode_state(
                    cfg, shape.global_batch,
                    shape.seq_len + _extra_prefix(cfg)))
            st_shard = decode_state_specs(state_sds, cfg, mesh, policy)

            def fn(params, batch, state):
                return model_registry.prefill(params, batch, cfg, state)

            lowered = jax.jit(
                fn, in_shardings=(p_shard, in_shard, st_shard),
                out_shardings=(None, st_shard), donate_argnums=(2,),
            ).lower(params_sds, specs, state_sds)
        else:  # decode
            state_sds = jax.eval_shape(
                lambda: model_registry.make_decode_state(
                    cfg, shape.global_batch,
                    shape.seq_len + _extra_prefix(cfg)))
            st_shard = decode_state_specs(state_sds, cfg, mesh, policy)

            def fn(params, token, state):
                return model_registry.decode_step(params, token, cfg, state)

            lowered = jax.jit(
                fn, in_shardings=(p_shard, in_shard["tokens"], st_shard),
                out_shardings=(None, st_shard), donate_argnums=(2,),
            ).lower(params_sds, specs["tokens"], state_sds)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    txt = compiled.as_text()
    costs = parse_hlo(txt)
    mesh_shape = tuple(mesh_override[0]) if mesh_override else (
        (2, 16, 16) if multi_pod else (16, 16))
    rep = roofline_terms(
        costs, arch=arch, shape=shape_name, mesh_shape=mesh_shape,
        model_flops=model_flops_estimate(cfg, shape))
    report = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(map(str, mesh_shape)),
        "status": "ok",
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "mem_args_gb": round(ma.argument_size_in_bytes / 2**30, 3),
        "mem_out_gb": round(ma.output_size_in_bytes / 2**30, 3),
        "mem_temp_gb": round(ma.temp_size_in_bytes / 2**30, 3),
        "mem_total_gb": round(
            (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes) / 2**30, 3),
        "xla_flops_raw": ca.get("flops", 0.0),
        "hlo_flops_scaled": rep.hlo_flops_per_chip,
        "hlo_bytes_scaled": rep.hlo_bytes_per_chip,
        "compute_ms": round(rep.compute_s * 1e3, 4),
        "memory_ms": round(rep.memory_s * 1e3, 4),
        "collective_ms": round(rep.collective_s * 1e3, 4),
        "dominant": rep.dominant,
        "collective_intra_gb": round(rep.collective_intra_bytes / 2**30, 4),
        "collective_cross_gb": round(rep.collective_cross_bytes / 2**30, 4),
        "n_collectives": rep.n_collectives,
        "n_while": costs.n_while,
        "model_flops": rep.model_flops_total,
        "useful_flops_ratio": round(rep.useful_flops_ratio, 4),
        "roofline_fraction": round(rep.roofline_fraction, 4),
        "attn_scope_bytes": costs.scope_bytes.get("attn_core", 0.0),
        "attn_scope_flops": costs.scope_flops.get("attn_core", 0.0),
    }
    from repro.analysis.roofline import flash_adjusted
    adj_mem_s, adj_frac = flash_adjusted(rep, costs, cfg, shape)
    report["memory_ms_flash"] = round(adj_mem_s * 1e3, 4)
    report["roofline_fraction_flash"] = round(adj_frac, 4)
    return report, compiled


def _extra_prefix(cfg) -> int:
    if cfg.family == Family.VLM:
        return cfg.img_tokens
    return 0


def run_cells(cells, *, multi_pod: bool, out_path: str | None):
    results = []
    for arch, shape_name in cells:
        tag = f"{arch} x {shape_name} ({'2x16x16' if multi_pod else '16x16'})"
        try:
            rep, compiled = lower_cell(arch, shape_name, multi_pod=multi_pod)
            del compiled
            print(f"[ok]   {tag}: mem={rep['mem_total_gb']:.2f}GB/dev "
                  f"dominant={rep['dominant']} "
                  f"compute={rep['compute_ms']:.3f}ms "
                  f"mem={rep['memory_ms']:.3f}ms "
                  f"coll={rep['collective_ms']:.3f}ms "
                  f"(compile {rep['compile_s']:.1f}s)")
        except ShapeNotSupported as e:
            rep = {"arch": arch, "shape": shape_name,
                   "mesh": "2x16x16" if multi_pod else "16x16",
                   "status": "skipped", "reason": str(e)}
            print(f"[skip] {tag}: {e}")
        except Exception as e:
            rep = {"arch": arch, "shape": shape_name,
                   "mesh": "2x16x16" if multi_pod else "16x16",
                   "status": "error", "reason": f"{type(e).__name__}: {e}"}
            print(f"[FAIL] {tag}: {type(e).__name__}: {e}")
        results.append(rep)
        if out_path:
            with open(out_path, "a") as f:
                f.write(json.dumps(rep) + "\n")
    return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from repro.configs import ARCHS
    if args.all:
        cells = [(a, s) for a in ARCHS for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        cells = [(args.arch, args.shape)]

    results = []
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for mp in meshes:
        results += run_cells(cells, multi_pod=mp, out_path=args.out)
    n_fail = sum(r["status"] == "error" for r in results)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    print(f"\ndry-run: {n_ok} ok, {n_skip} documented skips, {n_fail} FAILED")
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
