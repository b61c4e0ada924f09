"""Fused streaming attention Pallas kernel — stages 2+3 of the paper's MHA
pipeline (Sec. IV-A), adapted to the TPU memory hierarchy.

FPGA original: Q rows stream out of FIFOs against a register-resident K
(stage 2: scores + softmax), scores stream against a register-resident V
(stage 3: weighted sum) — the k x k score matrix never exists in slow
memory.  TPU adaptation: Q row-blocks stream through the grid while K/V
*blocks* are pinned in VMEM; scores live only in VREG/VMEM scratch; the
HBM->VMEM double-buffered grid pipeline is the FIFO chain.

Two softmax modes, matching ``core/softmax``:

* ``safe``  — online max/sum (flash) for the float path.
* ``lut``   — the paper's no-max-subtraction 3-stage LUT softmax over the
  bounded fixed-point score domain: running *sum* only, exp via the
  one-hot-MXU table read.  Numerically valid because scores are clipped to
  the exp-table domain, exactly like ap_fixed saturation on the FPGA.

Masking: none / causal / sliding-window (starcoder2) via block-level
index arithmetic.

Grid: ``(batch*heads, q_blocks, kv_blocks)`` with kv innermost sequential;
scratch (m, l, acc) persists across the kv dimension — the reuse-factor
analogue for attention is the kv_block count per q tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import lut
from repro.kernels import lut_read

NEG_INF = -1e30


def _make_kernel(
    *,
    scale: float,
    causal: bool,
    window: int | None,
    mode: str,
    block_q: int,
    block_kv: int,
    kv_len: int,
):
    def _kernel(
        q_ref, k_ref, v_ref, exp_tab_ref, inv_tab_ref, o_ref, m_ref, l_ref,
        acc_ref, *lut_scratch,
    ):
        kv_idx = pl.program_id(2)
        n_kv = pl.num_programs(2)
        q_idx = pl.program_id(1)

        @pl.when(kv_idx == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        q = q_ref[0].astype(jnp.float32)  # (block_q, d)
        k = k_ref[0].astype(jnp.float32)  # (block_kv, d)
        v = v_ref[0].astype(jnp.float32)  # (block_kv, d)

        # stage 2a: scores = Q K^T * 1/sqrt(d_k)  (pre-computed constant)
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (block_q, block_kv)

        # positional mask
        q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0
        )
        k_pos = kv_idx * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1
        )
        mask = k_pos < kv_len  # padding mask
        if causal:
            mask = mask & (k_pos <= q_pos)
        if window is not None:
            mask = mask & (q_pos - k_pos < window)

        if mode == "safe":
            s = jnp.where(mask, s, NEG_INF)
            # stage 2b: online softmax (running max/sum)
            m_prev = m_ref[...]  # (block_q, 1)
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            m_ref[...] = m_new
            # stage 3: weighted sum with V
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p, v, dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        else:  # paper's LUT mode: bounded domain, no max subtraction
            idx_ref, p_ref = lut_scratch
            idx_ref[...] = lut.lut_index(s, lut.EXP_SPEC)
            lut_read.read_rows(idx_ref, exp_tab_ref[...], p_ref)
            p = p_ref[...]
            p = jnp.where(mask, p, 0.0)
            l_ref[...] += jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[...] += jax.lax.dot_general(
                p, v, dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        @pl.when(kv_idx == n_kv - 1)
        def _epilogue():
            l = l_ref[...]
            if mode == "safe":
                inv = jnp.where(l > 0, 1.0 / jnp.maximum(l, 1e-30), 0.0)
            else:
                # paper stage 2: denominator reciprocal via the inversion LUT
                inv = lut_read.read_column(
                    lut.lut_index(l, lut.INV_SPEC), inv_tab_ref[...]
                )
                inv = jnp.where(l > 0, inv, 0.0)
            o_ref[0] = (acc_ref[...] * inv).astype(o_ref.dtype)

    return _kernel


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale", "causal", "window", "mode", "block_q", "block_kv",
        "kv_len", "interpret",
    ),
)
def flash_attention_pallas(
    q: jax.Array,  # (BH, Lq, D)
    k: jax.Array,  # (BH, Lkv, D)
    v: jax.Array,  # (BH, Lkv, D)
    *,
    scale: float,
    causal: bool = False,
    window: int | None = None,
    mode: str = "safe",
    block_q: int = 128,
    block_kv: int = 128,
    kv_len: int | None = None,  # true (unpadded) kv length
    interpret: bool = False,
) -> jax.Array:
    bh, lq, d = q.shape
    _, lkv, _ = k.shape
    kv_len = lkv if kv_len is None else kv_len
    block_q = min(block_q, lq)
    block_kv = min(block_kv, lkv)
    assert lq % block_q == 0 and lkv % block_kv == 0, (lq, lkv, block_q, block_kv)
    grid = (bh, lq // block_q, lkv // block_kv)
    exp_tab = lut.exp_table().reshape(1, -1)  # row: read_rows
    inv_tab = lut.inv_table().reshape(-1, 1)  # column: read_column
    lut_scratch = [
        pltpu.VMEM((block_q, block_kv), jnp.int32),  # exp-table indices
        pltpu.VMEM((block_q, block_kv), jnp.float32),  # exp-table values
    ] if mode == "lut" else []
    kernel = _make_kernel(
        scale=scale, causal=causal, window=window, mode=mode,
        block_q=block_q, block_kv=block_kv, kv_len=kv_len,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec(exp_tab.shape, lambda b, i, j: (0, 0)),
            pl.BlockSpec(inv_tab.shape, lambda b, i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, lq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),  # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),  # running sum l
            pltpu.VMEM((block_q, d), jnp.float32),  # output accumulator
        ] + lut_scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=f"flash_attention_{mode}",
    )(q, k, v, exp_tab, inv_tab)
