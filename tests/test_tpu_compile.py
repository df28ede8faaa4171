"""Compiles for a described TPU v5e (no chip attached): the full-width train
step and the Pallas kernels, refused here when the chip's compiler would
refuse them.  The topology is described inside a fixture, never at import,
so every test worker collects the same tests."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding

from repro.configs import get_config
from repro.distributed import sharding as sh
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.ssm_scan import ssm_scan_kernel
from repro.optim.adamw import AdamWConfig
from repro.train.loop import abstract_state, jit_train_step

#: One v5e chip's HBM.
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # Compiles for a described chip cannot be read back from the
    # persistent cache without one: keep them out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _mesh(topo, data, model):
    devs = np.array(topo.devices[:data * model]).reshape(data, model)
    return Mesh(devs, ("data", "model"), axis_types=(AxisType.Auto,) * 2)


def _peak_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


@pytest.mark.parametrize("data,model", [(1, 1), (2, 2)])
def test_qwen3_train_step_full_width_fits_hbm(topo, data, model):
    """The step train() runs, at qwen3-1.7b's published widths (1 layer,
    seq 1024), under the state shardings train() places."""
    cfg = dataclasses.replace(get_config("qwen3-1.7b"), n_layers=1)
    mesh = _mesh(topo, data, model)
    prev = sh.get_policy()
    sh.set_mesh(mesh)
    try:
        like = abstract_state(cfg, mesh)
        tok = jax.ShapeDtypeStruct(
            (data, 1024), jnp.int32,
            sharding=NamedSharding(mesh, sh.batch_spec(mesh, 2)))
        step = jit_train_step(cfg, AdamWConfig(), like, mesh)
        compiled = step.lower(like["params"], like["opt"],
                              {"tokens": tok, "labels": tok}).compile()
    finally:
        sh.set_mesh(prev.mesh, prev.sp_decode_axis)
    assert 0 < _peak_bytes(compiled) < HBM_BYTES
    if data * model > 1:
        assert "all-reduce" in compiled.as_text()


def test_flash_attention_kernel_compiles(one_chip):
    q = jax.ShapeDtypeStruct((1, 16, 4096, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8, 4096, 128), jnp.bfloat16,
                              sharding=one_chip)
    compiled = jax.jit(flash_attention_kernel).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssm_scan_kernel_compiles_falcon_mamba_width(one_chip):
    cfg = get_config("falcon-mamba-7b")
    assert (cfg.d_inner, cfg.ssm_state) == (8192, 16)
    x = jax.ShapeDtypeStruct((1, 256, cfg.d_inner, cfg.ssm_state),
                             jnp.float32, sharding=one_chip)
    c = jax.ShapeDtypeStruct((1, 256, cfg.ssm_state), jnp.float32,
                             sharding=one_chip)
    compiled = jax.jit(ssm_scan_kernel).lower(x, x, c).compile()
    assert "tpu_custom_call" in compiled.as_text()
