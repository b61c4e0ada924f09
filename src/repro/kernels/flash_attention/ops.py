"""jit'd public wrapper for the fused streaming attention kernel.

Accepts (batch, heads, len, d) tensors with GQA head-group broadcasting,
pads lengths to block multiples, and dispatches to the Pallas kernel or the
jnp reference.  This is the single attention entry point the model zoo uses
(``models/attention.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref


def _pad_len(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "mode", "use_pallas", "interpret",
        "block_q", "block_kv",
    ),
)
def mha(
    q: jax.Array,  # (B, Hq, Lq, D)
    k: jax.Array,  # (B, Hkv, Lkv, D)
    v: jax.Array,  # (B, Hkv, Lkv, D)
    *,
    causal: bool = False,
    window: int | None = None,
    mode: str = "safe",
    use_pallas: bool = False,
    interpret: bool = False,
    block_q: int = 128,
    block_kv: int = 128,
) -> jax.Array:
    b, hq, lq, d = q.shape
    _, hkv, lkv, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    scale = 1.0 / (d ** 0.5)

    # GQA: broadcast kv heads across the query-head groups.
    if group > 1:
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)

    if not use_pallas:
        # 4D path (no batch*head flatten): keeps head/batch shardings
        # intact under pjit — the flatten-reshape forces an involuntary
        # SPMD rematerialization on production meshes.
        return attention_ref(
            q, k, v, scale=scale, causal=causal, window=window, mode=mode
        )

    qf = q.reshape(b * hq, lq, d)
    kf = k.reshape(b * hq, lkv, d)
    vf = v.reshape(b * hq, lkv, d)

    bq = min(block_q, lq)
    bkv = min(block_kv, lkv)
    qp = _pad_len(qf, 1, bq)
    kp = _pad_len(kf, 1, bkv)
    vp = _pad_len(vf, 1, bkv)
    out = flash_attention_pallas(
        qp, kp, vp,
        scale=scale, causal=causal, window=window, mode=mode,
        block_q=bq, block_kv=bkv, kv_len=lkv, interpret=interpret,
    )
    return out[:, :lq].reshape(b, hq, lq, d)
