"""jit'd public wrapper for the LUT softmax kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import fixed_point as fxp
from repro.core import lut
from repro.kernels.lut_softmax.lut_softmax import lut_softmax_pallas
from repro.kernels.lut_softmax.ref import lut_softmax_ref


def _snap_output(out: jax.Array, precision) -> jax.Array:
    """Emit on an ap_fixed grid when a fixed output precision is given
    (the hardware datapath hands fixed-point rows to the next stage)."""
    if precision is None or getattr(precision, "kind", None) != "fixed":
        return out
    return fxp.quantize(out, precision.fixed_cfg())


@functools.partial(
    jax.jit, static_argnames=("use_pallas", "interpret", "precision")
)
def lut_softmax(
    x: jax.Array,
    *,
    use_pallas: bool = True,
    interpret: bool = False,
    precision=None,  # core.precision.Precision (fixed): output grid
) -> jax.Array:
    """Softmax over the last axis via the paper's 3-stage LUT dataflow.

    Accepts any leading batch shape; rows are padded to the block size.
    Fully-padded rows produce garbage that is sliced away.
    """
    if not use_pallas:
        return _snap_output(lut_softmax_ref(x), precision)

    *lead, k = x.shape
    rows = 1
    for d in lead:
        rows *= d
    x2 = x.reshape(rows, k)
    block_rows = min(64, rows) if rows % 64 != 0 else 64
    while rows % block_rows != 0:
        block_rows -= 1
    exp_tab = lut.exp_table().reshape(1, -1)
    inv_tab = lut.inv_table().reshape(-1, 1)
    out = lut_softmax_pallas(
        x2, exp_tab, inv_tab, block_rows=block_rows, interpret=interpret
    )
    return _snap_output(out.reshape(*lead, k), precision)
