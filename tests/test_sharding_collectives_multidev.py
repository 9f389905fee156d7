"""Sharding rules + collective schedules under a multi-device host mesh.

These need >1 device, so they run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=16 (conftest must NOT set
it globally — smoke tests see 1 device by design)."""

import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=16",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=560)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_param_specs_divisibility_rules():
    out = run_sub("""
        import jax, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro import compat
        from repro.configs import get_smoke_config, get_config
        from repro.models import init_params
        from repro.sharding.partition import param_specs, default_policy
        mesh = compat.make_mesh((4, 4), ("data", "model"))
        cfg = get_config("llama3-8b")
        params = jax.eval_shape(lambda: init_params(cfg, 0))
        specs = param_specs(params, cfg, mesh)
        blocks = specs["blocks"]
        assert blocks["attn"]["wq"].spec == P(None, None, "model"), blocks["attn"]["wq"].spec
        assert blocks["attn"]["wo"].spec == P(None, "model", None)
        assert blocks["mlp"]["w_in"].spec == P(None, None, "model")
        assert specs["embed"].spec == P("model", None)
        assert specs["ln_f"].spec == P()
        # paligemma kv=1: wk head dim = 1*256 = 256 divisible by 4 -> sharded
        cfg2 = get_config("paligemma-3b")
        p2 = jax.eval_shape(lambda: init_params(cfg2, 0))
        s2 = param_specs(p2, cfg2, mesh)
        assert s2["blocks"]["attn"]["wk"].spec == P(None, None, "model")
        print("OK")
        """)
    assert "OK" in out


def test_moe_expert_parallel_specs():
    out = run_sub("""
        import jax
        from jax.sharding import PartitionSpec as P
        from repro import compat
        from repro.configs import get_config
        from repro.models import init_params
        from repro.sharding.partition import param_specs
        mesh = compat.make_mesh((4, 4), ("data", "model"))
        cfg = get_config("granite-moe-3b-a800m")   # 40 experts % 4 == 0
        params = jax.eval_shape(lambda: init_params(cfg, 0))
        specs = param_specs(params, cfg, mesh)
        assert specs["blocks"]["moe"]["w_in"].spec == P(None, "model", None, None)
        assert specs["blocks"]["moe"]["router"].spec == P(None, None, None)
        print("OK")
        """)
    assert "OK" in out


def test_allreduce_schedules_agree():
    out = run_sub("""
        import jax, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro import compat
        from repro.collectives import allreduce_direct, allreduce_hierarchical
        mesh = compat.make_mesh((2, 2, 4), ("pod", "data", "model"))
        x = np.random.default_rng(0).standard_normal((16, 8, 3)).astype(np.float32)
        def run(fn):
            return jax.shard_map(fn, mesh=mesh,
                                 in_specs=P(("pod", "data", "model")),
                                 out_specs=P(("pod", "data", "model")),
                                 check_vma=False)(x)
        d = run(lambda v: allreduce_direct(v, ("pod", "data")))
        h = run(lambda v: allreduce_hierarchical(v, "pod", "data", 2))
        np.testing.assert_allclose(np.asarray(d), np.asarray(h), rtol=1e-6)
        print("OK")
        """)
    assert "OK" in out


def test_alltoall_schedules_roundtrip():
    out = run_sub("""
        import jax, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro import compat
        from repro.collectives import alltoall_direct, alltoall_hierarchical
        mesh = compat.make_mesh((2, 2, 4), ("pod", "data", "model"))
        y = np.arange(64*4, dtype=np.float32).reshape(64, 4)
        da = jax.shard_map(lambda v: alltoall_direct(v, "model"), mesh=mesh,
                              in_specs=P(("pod", "data", "model")),
                              out_specs=P(("pod", "data", "model")),
                              check_vma=False)(y)
        # a2a is an involution on 2 axes of equal split: applying the
        # direct exchange twice restores the input
        da2 = jax.shard_map(lambda v: alltoall_direct(alltoall_direct(v, "model"), "model"),
                               mesh=mesh, in_specs=P(("pod", "data", "model")),
                               out_specs=P(("pod", "data", "model")),
                               check_vma=False)(y)
        np.testing.assert_allclose(np.asarray(da2), y)
        h = jax.shard_map(lambda v: alltoall_hierarchical(v, "pod", "data"),
                             mesh=mesh, in_specs=P(("pod", "data", "model")),
                             out_specs=P(("pod", "data", "model")),
                             check_vma=False)(y)
        assert np.asarray(h).shape == y.shape
        print("OK")
        """)
    assert "OK" in out


def test_grad_allreduce_means_over_dp():
    out = run_sub("""
        import jax, numpy as np
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro import compat
        from repro.collectives import grad_allreduce
        from repro.collectives.modes import CollectiveMode
        mesh = compat.make_mesh((2, 2, 4), ("pod", "data", "model"))
        g = {"w": jnp.ones((8, 4))}
        for mode in (CollectiveMode.DIRECT, CollectiveMode.HIERARCHICAL):
            out = grad_allreduce(g, mesh, mode=mode)
            np.testing.assert_allclose(np.asarray(out["w"]), 1.0)
        print("OK")
        """)
    assert "OK" in out


def test_elastic_reshard_to_new_mesh():
    out = run_sub("""
        import jax, numpy as np
        from repro import compat
        from repro.configs import get_smoke_config
        from repro.models import init_params
        from repro.ckpt.elastic import reshard_checkpoint
        cfg = get_smoke_config("llama3-8b")
        params = init_params(cfg, 0)
        host = jax.tree_util.tree_map(np.asarray, params)
        mesh_small = compat.make_mesh((2, 2), ("data", "model"))
        mesh_big = compat.make_mesh((4, 4), ("data", "model"))
        a = reshard_checkpoint(host, cfg, mesh_small)
        b = reshard_checkpoint(host, cfg, mesh_big)
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        print("OK")
        """)
    assert "OK" in out
