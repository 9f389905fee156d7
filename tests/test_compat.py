"""repro.compat — the helpers the repo keeps over the installed jax.

  * `make_mesh` gives every axis AxisType.Auto, where `jax.make_mesh`
    defaults to Explicit; `abstract_axis_sizes` reads the active mesh;
  * `on_tpu` / `resolve_pallas_kernel` pick the segment-sum of the jax
    phase engine;
  * `enable_compile_cache` places JAX's persistent compilation cache:
    the environment variable wins, else one fixed in-checkout path.
"""

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.compat import runtime

REPO = pathlib.Path(__file__).resolve().parent.parent


# --------------------------------------------------------------------------
# Meshes on the installed jax.
# --------------------------------------------------------------------------
def test_make_mesh_and_set_mesh_roundtrip():
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_names == ("data", "model")
    assert compat.abstract_axis_sizes() == {}          # outside set_mesh
    with jax.set_mesh(mesh):
        assert compat.abstract_axis_sizes() == {"data": 1, "model": 1}
        am = jax.sharding.get_abstract_mesh()
        assert tuple(am.axis_names) == ("data", "model")
    assert compat.abstract_axis_sizes() == {}


def test_make_mesh_passes_auto_axis_types():
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)
    # the wrapper exists because jax's own default differs
    assert jax.make_mesh((1, 1), ("data", "model")).axis_types \
        != mesh.axis_types


def test_shard_map_runs_collective():
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    f = jax.shard_map(lambda x: jax.lax.psum(x, "model"), mesh=mesh,
                      in_specs=P(), out_specs=P(), check_vma=False)
    np.testing.assert_allclose(np.asarray(f(jnp.ones((4,)))), 1.0)


# --------------------------------------------------------------------------
# TPU detection + the pallas_kernel knob's tri-state resolution.
# --------------------------------------------------------------------------
def test_on_tpu_matches_default_backend():
    assert compat.on_tpu() == (jax.default_backend() == "tpu")


def test_resolve_pallas_kernel_auto_follows_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert compat.resolve_pallas_kernel("auto") is True
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert compat.resolve_pallas_kernel("auto") is False


def test_resolve_pallas_kernel_forced_ignores_hardware(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert compat.resolve_pallas_kernel("on") is True
    assert compat.resolve_pallas_kernel("off") is False
    with pytest.raises(ValueError):
        compat.resolve_pallas_kernel("banana")


# --------------------------------------------------------------------------
# Compile-cache placement (jax.config.update is recorded, never applied:
# tests leave the persistent cache off).
# --------------------------------------------------------------------------
@pytest.fixture
def config_updates(monkeypatch):
    seen = []
    monkeypatch.setattr(runtime.jax.config, "update",
                        lambda name, value: seen.append((name, value)))
    return seen


def test_compile_cache_env_var_wins(monkeypatch, tmp_path, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compat.enable_compile_cache() == str(tmp_path)
    assert config_updates == []         # JAX reads the variable itself


def test_compile_cache_falls_back_to_fixed_repo_dir(monkeypatch,
                                                    config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    where = compat.enable_compile_cache()
    assert where == str(REPO / ".jax_cache")
    assert config_updates == [("jax_compilation_cache_dir", where)]
    assert compat.enable_compile_cache() == where     # never moves


def test_compile_cache_dir_is_ignored_by_git():
    assert runtime.COMPILE_CACHE_DIR == REPO / ".jax_cache"
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()
