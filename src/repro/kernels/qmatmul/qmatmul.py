"""int8 x int8 -> int32 GEMM Pallas kernel with dequantizing epilogue.

TPU adaptation of the paper's fixed-point DSP datapath (Sec. VI-A): weights
and activations are int8 codes (ap_fixed<8,I> after scale folding), products
accumulate in int32 (the paper's wide accumulator), and the epilogue applies
``x_scale * w_scale`` (+ optional bias) to produce float output.

Grid: ``(grid_m, grid_n, grid_k)`` with the contraction dim innermost and
*sequential* — ``grid_k`` IS the paper's reuse factor: R=1 streams the whole
K per output tile (fully parallel), R>1 time-multiplexes the MXU over R
chunks with an R-fold smaller VMEM working set (see ``core/reuse.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _qmatmul_kernel(x_ref, w_ref, xs_ref, ws_ref, o_ref, acc_ref):
    """One (block_m, block_n) output tile; revisited across grid_k steps."""
    k_idx = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 x int8 -> int32 on the MXU (paper: DSP multiply, wide accumulate).
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...],
        w_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k_idx == nk - 1)
    def _epilogue():
        # dequant: per-row activation scale x per-col weight scale.
        scale = xs_ref[...] * ws_ref[...]  # (block_m,1)*(1,block_n)
        o_ref[...] = (acc_ref[...].astype(jnp.float32) * scale).astype(
            o_ref.dtype
        )


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret", "out_dtype"),
)
def qmatmul_pallas(
    x: jax.Array,  # (M, K) int8
    w: jax.Array,  # (K, N) int8
    x_scale: jax.Array,  # (M, 1) f32 per-row
    w_scale: jax.Array,  # (1, N) f32 per-col
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        f"shapes ({m},{k},{n}) must divide blocks "
        f"({block_m},{block_k},{block_n}); pad in ops.py"
    )
    grid = (m // block_m, n // block_n, k // block_k)
    return pl.pallas_call(
        _qmatmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_m, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="qmatmul_int8",
    )(x, w, x_scale, w_scale)
