"""Paper's 5-stage LayerNorm as a fused Pallas TPU kernel (Sec. IV-C).

Stages (all fused in one VMEM-resident pass per row-block):
  1. mean = sum(x)/k
  2. dm   = x - mean
  3. var  = sum(dm^2)/k
  4. x_hat = dm * rsqrt(var)      (optionally via the 1/sqrt LUT)
  5. out  = gamma * x_hat + beta

The FPGA version streams one time step per cycle through five pipeline
registers; the TPU version processes a block of rows per grid step with the
whole feature dim resident in VMEM — the HBM->VMEM grid pipeline plays the
role of the FIFO chain.  RMSNorm mode fixes the mean at zero (stages 3-5),
covering the RMSNorm used by most assigned LM architectures.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import lut
from repro.kernels import lut_read


def _make_kernel(use_lut: bool, rms: bool, eps: float):
    def _kernel(x_ref, gamma_ref, beta_ref, rsqrt_tab_ref, o_ref):
        x = x_ref[...].astype(jnp.float32)
        k = x.shape[-1]
        if rms:
            dm = x  # stage 1-2 skipped: mean fixed at 0
        else:
            mean = jnp.sum(x, axis=-1, keepdims=True) / k  # stage 1
            dm = x - mean  # stage 2
        var = jnp.sum(dm * dm, axis=-1, keepdims=True) / k  # stage 3
        if use_lut:  # stage 4 via LUT (one-hot MXU read)
            inv_std = lut_read.read_column(
                lut.lut_index(var, lut.RSQRT_SPEC), rsqrt_tab_ref[...]
            )
        else:
            inv_std = jax.lax.rsqrt(var + eps)
        x_hat = dm * inv_std
        out = x_hat * gamma_ref[...]  # stage 5
        if not rms:
            out = out + beta_ref[...]
        o_ref[...] = out.astype(o_ref.dtype)

    return _kernel


@functools.partial(
    jax.jit,
    static_argnames=("block_rows", "use_lut", "rms", "eps", "interpret"),
)
def layernorm_pallas(
    x: jax.Array,  # (R, K)
    gamma: jax.Array,  # (1, K)
    beta: jax.Array,  # (1, K)
    rsqrt_table: jax.Array,  # (T, 1)
    *,
    block_rows: int = 64,
    use_lut: bool = False,
    rms: bool = False,
    eps: float = 1e-5,
    interpret: bool = False,
) -> jax.Array:
    rows, k = x.shape
    assert rows % block_rows == 0, (rows, block_rows)
    grid = (rows // block_rows,)
    return pl.pallas_call(
        _make_kernel(use_lut, rms, eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, k), lambda i: (i, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
            pl.BlockSpec(rsqrt_table.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, k), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
        name="layernorm_staged",
    )(x, gamma, beta, rsqrt_table)
