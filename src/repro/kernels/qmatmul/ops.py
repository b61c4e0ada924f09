"""jit'd public wrapper for the int8 GEMM kernel.

Handles quantization of float inputs, padding to block multiples, the
reuse-factor -> block_k mapping, and falls back to the jnp reference on
hosts where Pallas interpret mode is not wanted (the wrapper is what the
models call; kernels are the TPU target).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import quant, reuse
from repro.kernels.qmatmul.qmatmul import qmatmul_pallas
from repro.kernels.qmatmul.ref import qmatmul_ref


def _pad_to(x: jax.Array, m0: int, m1: int) -> jax.Array:
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


@functools.partial(
    jax.jit,
    static_argnames=(
        "reuse_factor", "strategy", "use_pallas", "interpret", "precision"
    ),
)
def qmatmul(
    x: jax.Array,  # (M, K) float
    w: jax.Array,  # (K, N) float
    *,
    reuse_factor: int = 1,
    strategy: reuse.Strategy = reuse.Strategy.LATENCY,
    use_pallas: bool = True,
    interpret: bool = False,
    precision=None,  # core.precision.Precision (int8 kind): bits/granularity
) -> jax.Array:
    """Quantize x (per-row) and w (per-col) to int8 and multiply.

    The paper's reuse factor R maps to grid_k sequential contraction chunks
    (``core/reuse.plan_matmul``).  ``precision`` threads a PrecisionPlan
    weights entry into the quantizer: ``bits`` selects the code width and
    ``per_channel=False`` collapses to per-tensor scales.
    """
    bits = 8
    per_channel = True
    if precision is not None:
        if precision.kind != "int8":
            raise ValueError(
                f"qmatmul expects an int8 precision, got {precision}"
            )
        bits = precision.bits
        per_channel = precision.per_channel
    m, k = x.shape
    _, n = w.shape
    xq = quant.quantize_int8(
        x, axis=0 if per_channel else None, bits=bits
    )  # per-row scales
    wq = quant.quantize_int8(
        w, axis=1 if per_channel else None, bits=bits
    )  # per-col scales
    x_scale = jnp.broadcast_to(xq.scale.reshape(-1, 1), (m, 1))
    w_scale = jnp.broadcast_to(wq.scale.reshape(1, -1), (1, n))

    if not use_pallas:
        return qmatmul_ref(xq.values, wq.values, x_scale, w_scale)

    plan = reuse.plan_matmul(
        m, k, n, reuse_factor=reuse_factor, strategy=strategy, bytes_per_elem=1
    )
    xv = _pad_to(xq.values, plan.block_m, plan.block_k)
    wv = _pad_to(wq.values, plan.block_k, plan.block_n)
    xs = _pad_to(x_scale, plan.block_m, 1)
    ws = _pad_to(w_scale, 1, plan.block_n)
    out = qmatmul_pallas(
        xv,
        wv,
        xs,
        ws,
        block_m=plan.block_m,
        block_n=plan.block_n,
        block_k=plan.block_k,
        interpret=interpret,
    )
    return out[:m, :n]


def qmatmul_prequantized(
    xq: quant.QTensor, wq: quant.QTensor, out_dtype=jnp.float32
) -> jax.Array:
    """Reference path for already-quantized tensors (serving engine)."""
    m = xq.values.shape[0]
    n = wq.values.shape[1]
    xs = (
        xq.scale.reshape(m, 1)
        if xq.axis is not None
        else jnp.full((m, 1), xq.scale)
    )
    ws = (
        wq.scale.reshape(1, n)
        if wq.axis is not None
        else jnp.full((1, n), wq.scale)
    )
    return qmatmul_ref(xq.values, wq.values, xs, ws, out_dtype)
