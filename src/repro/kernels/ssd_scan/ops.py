"""jit'd public wrapper for the SSD scan kernel: (b, l, h, p)-layout entry
point used by models/ssm when the Pallas path is selected."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan.ref import ssd_scan_ref
from repro.kernels.ssd_scan.ssd_scan import ssd_scan_pallas


@functools.partial(jax.jit, static_argnames=("chunk", "use_pallas", "interpret"))
def ssd(
    xdt: jax.Array,  # (b, l, h, p)
    a: jax.Array,  # (b, l, h)
    bmat: jax.Array,  # (b, l, h, n)
    cmat: jax.Array,  # (b, l, h, n)
    *,
    chunk: int = 64,
    use_pallas: bool = True,
    interpret: bool = False,
) -> jax.Array:
    b, l, h, p = xdt.shape
    n = bmat.shape[-1]
    chunk = min(chunk, l)

    def fold(t):
        return t.transpose(0, 2, 1, 3).reshape(b * h, l, t.shape[-1])

    xf = fold(xdt)
    af = fold(a[..., None])
    bf = fold(bmat)
    cf = fold(cmat)
    if use_pallas:
        out = ssd_scan_pallas(xf, af, bf, cf, chunk=chunk, interpret=interpret)
    else:
        out = ssd_scan_ref(xf, af, bf, cf, chunk=chunk)
    return out.reshape(b, h, l, p).transpose(0, 2, 1, 3)
