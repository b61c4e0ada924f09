"""Paper's 3-stage restructured softmax as a Pallas TPU kernel (Sec. IV-B).

    S_i = exp(z_i) * ( sum_j exp(z_j) )^{-1}

Stage 1: element-wise exp via lookup table.
Stage 2: row sum + reciprocal via lookup table (once per row).
Stage 3: element-wise multiply.

LUT realization on TPU: a BRAM read becomes a one-hot row-select executed on
the MXU — ``one_hot(idx, T) @ table`` — the natural systolic translation of
a table lookup (see DESIGN.md hardware-adaptation table).  No max
subtraction, exactly as in the paper: the fixed-point input domain is
bounded and the index computation saturates (AP_SAT).

Grid: one dimension over row-blocks; each block holds ``(block_rows, K)`` in
VMEM and produces its output in a single pass (latency strategy: II = 1
row-block per grid step).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import lut
from repro.kernels import lut_read


def _lut_softmax_kernel(x_ref, exp_tab_ref, inv_tab_ref, o_ref, idx_ref, e_ref):
    # Stage 1: exp LUT (element-wise).
    idx_ref[...] = lut.lut_index(x_ref[...].astype(jnp.float32), lut.EXP_SPEC)
    lut_read.read_rows(idx_ref, exp_tab_ref[...], e_ref)
    e = e_ref[...]
    # Stage 2: row sum, then inversion LUT (once per row).
    s = jnp.sum(e, axis=-1, keepdims=True)
    inv = lut_read.read_column(lut.lut_index(s, lut.INV_SPEC), inv_tab_ref[...])
    # Stage 3: element-wise multiply.
    o_ref[...] = (e * inv).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def lut_softmax_pallas(
    x: jax.Array,  # (R, K) scores
    exp_table: jax.Array,  # (1, T_exp) row
    inv_table: jax.Array,  # (T_inv, 1) column
    *,
    block_rows: int = 64,
    interpret: bool = False,
) -> jax.Array:
    rows, k = x.shape
    assert rows % block_rows == 0, (rows, block_rows)
    grid = (rows // block_rows,)
    return pl.pallas_call(
        _lut_softmax_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, k), lambda i: (i, 0)),
            pl.BlockSpec(exp_table.shape, lambda i: (0, 0)),
            pl.BlockSpec(inv_table.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, k), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_rows, k), jnp.int32),  # exp-table indices
            pltpu.VMEM((block_rows, k), jnp.float32),  # exp-table values
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
        name="lut_softmax",
    )(x, exp_table, inv_table)
