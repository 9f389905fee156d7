"""MoE paths (einsum vs EP) and gradient-communication utilities."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.collectives.moe_ep import moe_ep, moe_ep_ref
from repro.collectives.modes import CollectiveMode
from repro.collectives.selector import AppAwareSelector, ICICostModel, MeshSpec
from repro.models.common import Family, ModelConfig
from repro.models.moe import init_moe, moe_einsum
from repro.train.grad_comm import (GradCommConfig, bucketize,
                                   compress_decompress, select_bucket_modes)


def moe_cfg(**kw):
    base = dict(name="t", family=Family.MOE, n_layers=1, d_model=32,
                n_heads=4, n_kv_heads=2, d_ff=64, d_ff_expert=64,
                vocab=128, n_experts=8, top_k=2, remat=False)
    base.update(kw)
    return ModelConfig(**base)


def test_moe_einsum_finite_and_aux():
    cfg = moe_cfg()
    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 16, 32)),
                    jnp.float32)
    y, aux = moe_einsum(p, x, cfg)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()
    assert float(aux) >= 1.0 - 1e-3   # load-balance loss >= 1 at optimum


def test_moe_ep_matches_ref_on_trivial_mesh():
    cfg = moe_cfg(moe_impl="ep")
    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 16, 32)),
                    jnp.float32)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        y_ep, aux_ep = jax.jit(lambda p, x: moe_ep(p, x, cfg))(p, x)
    y_ref, aux_ref = moe_ep_ref(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(float(aux_ep), float(aux_ref), rtol=1e-4)


def test_moe_ep_grads_finite():
    cfg = moe_cfg(moe_impl="ep")
    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((2, 8, 32)),
                    jnp.float32)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    with jax.set_mesh(mesh):
        g = jax.jit(jax.grad(lambda p, x: moe_ep(p, x, cfg)[0].sum()))(p, x)
    for leaf in jax.tree_util.tree_leaves(g):
        assert np.isfinite(np.asarray(leaf)).all()


# --------------------------------------------------------------- grad_comm
def test_bucketize_respects_size():
    grads = {f"w{i}": jnp.zeros((1024,)) for i in range(10)}  # 4 KiB each
    buckets = bucketize(grads, bucket_bytes=8 * 1024)
    assert all(len(b) <= 2 for b in buckets)
    assert sorted(i for b in buckets for i in b) == list(range(10))


def test_error_feedback_is_lossless_in_aggregate():
    """EF invariant: wire + residual == accumulated true gradient."""
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal(512) * 1e-3, jnp.float32)
    res = jnp.zeros(512)
    total_wire = jnp.zeros(512)
    for _ in range(20):
        wire, res = compress_decompress(g, res)
        total_wire = total_wire + wire
    np.testing.assert_allclose(np.asarray(total_wire + res),
                               np.asarray(g * 20), rtol=1e-3, atol=1e-5)


def test_select_bucket_modes_uses_algorithm1():
    sel = AppAwareSelector(ICICostModel(MeshSpec(n_pods=2, inner_chips=256)))
    grads = {"big": jnp.zeros((64 << 20) // 4), "small": jnp.zeros(128)}
    modes = select_bucket_modes(sel, grads, GradCommConfig())
    assert len(modes) >= 1
    assert all(m in (CollectiveMode.DIRECT, CollectiveMode.HIERARCHICAL)
               for _, m in modes)
