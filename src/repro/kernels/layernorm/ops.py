"""jit'd public wrapper for the staged LayerNorm kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import fixed_point as fxp
from repro.core import lut
from repro.kernels.layernorm.layernorm import layernorm_pallas
from repro.kernels.layernorm.ref import layernorm_ref


def _snap_output(out: jax.Array, precision) -> jax.Array:
    """Emit on an ap_fixed grid when a fixed output precision is given
    (paper Sec. IV-C: the staged norm feeds a fixed-point datapath)."""
    if precision is None or getattr(precision, "kind", None) != "fixed":
        return out
    return fxp.quantize(out, precision.fixed_cfg())


@functools.partial(
    jax.jit,
    static_argnames=(
        "use_lut", "rms", "eps", "use_pallas", "interpret", "precision"
    ),
)
def layernorm(
    x: jax.Array,  # (..., K)
    gamma: jax.Array,  # (K,)
    beta: jax.Array | None = None,  # (K,) or None for RMSNorm
    *,
    use_lut: bool = False,
    rms: bool = False,
    eps: float = 1e-5,
    use_pallas: bool = True,
    interpret: bool = False,
    precision=None,  # core.precision.Precision (fixed): output grid
) -> jax.Array:
    k = x.shape[-1]
    if beta is None:
        beta = jnp.zeros((k,), dtype=jnp.float32)
    if not use_pallas:
        return _snap_output(
            layernorm_ref(x, gamma, beta, use_lut=use_lut, rms=rms, eps=eps),
            precision,
        )
    *lead, _ = x.shape
    rows = 1
    for d in lead:
        rows *= d
    x2 = x.reshape(rows, k)
    block_rows = 64 if rows % 64 == 0 else 1
    out = layernorm_pallas(
        x2,
        gamma.reshape(1, k).astype(jnp.float32),
        beta.reshape(1, k).astype(jnp.float32),
        lut.rsqrt_table().reshape(-1, 1),
        block_rows=block_rows,
        use_lut=use_lut,
        rms=rms,
        eps=eps,
        interpret=interpret,
    )
    return _snap_output(out.reshape(*lead, k), precision)
