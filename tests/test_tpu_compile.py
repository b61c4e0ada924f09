"""Compile the main-path Pallas kernels for a described TPU v5e chip at
minicpm-2b widths (d_model 2304, 36 heads of 64, d_ff 5760).

Nothing runs: the TPU compiler is handed shapes for a chip that is
described, not attached, and refuses what Mosaic would refuse on the
chip (unsupported shape casts, misaligned slices, too much VMEM) —
faults that interpret mode cannot see.  The topology is described inside
a fixture, never at import: only one process may load the TPU library,
and every test worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import mha
from repro.kernels.layernorm import layernorm
from repro.kernels.lut_softmax import lut_softmax
from repro.kernels.qmatmul import qmatmul

D_MODEL, N_HEADS, HEAD_DIM, D_FF = 2304, 36, 64, 5760
BATCH, SEQ = 4, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _hlo(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("mode", ["safe", "lut"])
def test_flash_attention_compiles(one_chip, mode):
    qkv = jax.ShapeDtypeStruct(
        (BATCH, N_HEADS, SEQ, HEAD_DIM), jnp.bfloat16, sharding=one_chip
    )
    hlo = _hlo(
        lambda q, k, v: mha(q, k, v, causal=True, mode=mode, use_pallas=True),
        qkv, qkv, qkv,
    )
    assert "tpu_custom_call" in hlo


def test_lut_softmax_compiles(one_chip):
    scores = jax.ShapeDtypeStruct(
        (N_HEADS * 128, SEQ), jnp.float32, sharding=one_chip
    )
    assert "tpu_custom_call" in _hlo(lut_softmax, scores)


@pytest.mark.parametrize("use_lut", [False, True])
def test_rmsnorm_compiles(one_chip, use_lut):
    x = jax.ShapeDtypeStruct((BATCH * SEQ, D_MODEL), jnp.bfloat16,
                             sharding=one_chip)
    g = jax.ShapeDtypeStruct((D_MODEL,), jnp.float32, sharding=one_chip)
    hlo = _hlo(lambda x, g: layernorm(x, g, rms=True, use_lut=use_lut), x, g)
    assert "tpu_custom_call" in hlo


def test_qmatmul_compiles(one_chip):
    x = jax.ShapeDtypeStruct((SEQ, D_MODEL), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((D_MODEL, D_FF), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _hlo(qmatmul, x, w)
