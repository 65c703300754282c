"""Nothing hides the device (ISSUE 21 D/E/F).

Backend -> accelerator is a strict map, the mesh of a TPU device set comes
from ``mesh_utils`` or fails, and the compile cache goes where the
environment says or to one fixed path. The JAX spellings the package shares
(``utils/jax_compat.py``) are the installed JAX's.
"""

import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu.accelerator import real_accelerator
from deepspeed_tpu.runtime.topology import MeshTopology
from deepspeed_tpu.utils import compile_cache, jax_compat

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


# -- backend -> accelerator ---------------------------------------------------

@pytest.fixture
def fresh_accelerator(monkeypatch):
    monkeypatch.setattr(real_accelerator, "_ACCELERATOR", None)
    monkeypatch.delenv(real_accelerator.ACCELERATOR_ENV, raising=False)


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_backend_maps_to_its_accelerator(fresh_accelerator, monkeypatch,
                                         backend):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert real_accelerator.get_accelerator()._name == backend


def test_unknown_backend_is_an_error(fresh_accelerator, monkeypatch):
    # no "anything that is not a CPU must be a TPU", no fallback to "cpu"
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(ValueError, match="gpu"):
        real_accelerator.get_accelerator()


def test_backend_failure_propagates(fresh_accelerator, monkeypatch):
    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(jax, "default_backend", boom)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        real_accelerator.get_accelerator()


# -- mesh layout --------------------------------------------------------------

def _fake_tpus(n):
    return [types.SimpleNamespace(platform="tpu", id=i, slice_index=0)
            for i in range(n)]


def test_tpu_mesh_comes_from_mesh_utils(monkeypatch):
    from jax.experimental import mesh_utils
    devs = _fake_tpus(4)
    calls = []

    def create(shape, devices):
        calls.append(shape)
        return np.asarray(devices[::-1]).reshape(shape)
    monkeypatch.setattr(mesh_utils, "create_device_mesh", create)
    grid = MeshTopology._device_grid(devs, (1, 4, 1, 1, 1, 1))
    assert calls == [(1, 4, 1, 1, 1, 1)]
    assert [d.id for d in grid.flat] == [3, 2, 1, 0]


def test_tpu_mesh_layout_failure_is_an_error(monkeypatch):
    # seed: `except Exception: pass` fell through to enumeration order
    from jax.experimental import mesh_utils

    def refuse(shape, devices):
        raise AssertionError("cannot lay out these devices")
    monkeypatch.setattr(mesh_utils, "create_device_mesh", refuse)
    with pytest.raises(AssertionError, match="cannot lay out"):
        MeshTopology._device_grid(_fake_tpus(4), (1, 4, 1, 1, 1, 1))


def test_one_device_and_cpu_meshes_are_plain_reshapes(eight_devices):
    one = MeshTopology._device_grid(_fake_tpus(1), (1,) * 6)
    assert one.shape == (1,) * 6
    grid = MeshTopology._device_grid(eight_devices, (1, 8, 1, 1, 1, 1))
    assert [d.id for d in grid.flat] == [d.id for d in eight_devices]


# -- compile cache ------------------------------------------------------------

@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_env_wins_and_nothing_is_set_in_code(monkeypatch, tmp_path,
                                                   cache_config):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None


def test_cache_defaults_to_the_checkout(monkeypatch, cache_config):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    want = os.path.join(ROOT, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # fixed: the path is part of the cache key
    assert compile_cache.enable_compile_cache() == want


def test_cache_dir_the_user_configured_is_left_alone(monkeypatch, tmp_path,
                                                     cache_config):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)


def test_cache_dir_is_ignored_by_git():
    with open(os.path.join(ROOT, ".gitignore")) as f:
        ignored = f.read().split()
    assert ".jax_cache/" in ignored and "chiprun_out/" in ignored


# -- the installed JAX's spellings --------------------------------------------

def test_shard_map_is_jax_shard_map():
    assert jax_compat.shard_map is jax.shard_map


@pytest.mark.parametrize("axes,size", [
    ("data", 4), (("data", "model"), 8), (["model", "data"], 8)])
def test_axis_size_inside_shard_map(eight_devices, axes, size):
    mesh = Mesh(np.asarray(eight_devices).reshape(4, 2), ("data", "model"))
    seen = []

    def body(x):
        seen.append((jax_compat.axis_size(axes),
                     jax_compat.in_manual_axes()))
        return x
    jax_compat.shard_map(body, mesh=mesh, in_specs=P("data", "model"),
                         out_specs=P("data", "model"))(jnp.zeros((4, 2)))
    assert seen == [(size, True)]


def test_sharding_constraint_degrades_outside_a_mesh():
    assert not jax_compat.in_manual_axes()
    x = jnp.ones((4,))
    assert jax_compat.with_sharding_constraint(x, P("data")) is x


# -- kernel gates read the backend, nothing else ------------------------------

@pytest.mark.parametrize("env,ctx,want", [
    ("1", {"backend": "tpu"}, True),
    ("1", {"backend": "tpu", "position": "alibi"}, False),
    ("1", {"backend": "cpu"}, False),
    ("0", {"backend": "tpu"}, False),
])
def test_paged_decode_registry_gate(monkeypatch, env, ctx, want):
    # opt-in + TPU backend; no import probe for a JAX that is not installed
    from deepspeed_tpu.inference.v2.modules.registry import \
        _pallas_paged_supported
    monkeypatch.setenv("DSTPU_PALLAS_PAGED", env)
    assert _pallas_paged_supported(ctx) is want


@pytest.mark.parametrize("backend,mode,interpret", [
    ("tpu", "pallas", False), ("cpu", "xla", True)])
def test_kernel_gates_follow_the_backend(monkeypatch, backend, mode,
                                         interpret):
    # on a TPU: compiled Pallas, never interpreted; on the CPU: XLA, and
    # a forced kernel runs interpreted
    from deepspeed_tpu.inference.v2.kernels.ragged_paged_attention import \
        _pallas_wave_default
    from deepspeed_tpu.ops.adam import pallas_adam
    from deepspeed_tpu.ops.transformer import pallas_flash
    for var in ("DSTPU_OPT_KERNEL", "DSTPU_QUANT_KERNEL", "DSTPU_RAGGED_ATTN"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert pallas_adam.opt_kernel_mode() == mode
    assert pallas_adam.opt_kernel_mode("DSTPU_QUANT_KERNEL") == mode
    assert pallas_adam.opt_kernel_interpret() is interpret
    assert pallas_flash._auto_interpret() is interpret
    assert _pallas_wave_default() is (backend == "tpu")


def test_op_report_reads_the_backend():
    from deepspeed_tpu.env_report import op_report
    rows = {name: ok for name, ok, _ in op_report()}
    assert rows["flash_attention (pallas)"] is False    # CPU backend here


def test_moe_route_refuses_an_unsplittable_token_axis():
    from deepspeed_tpu.ops.transformer import pallas_moe
    with pytest.raises(ValueError, match="1000 tokens"):
        pallas_moe.moe_route(jnp.zeros((1000, 4)), top_k=2, capacity=8,
                             interpret=True)
