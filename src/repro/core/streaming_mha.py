"""The paper's 4-stage streaming MHA pipeline (Sec. IV-A), module API.

Stage 1: Q/K/V linear projections     -> kernels/qmatmul (int8) or jnp
Stage 2: Q K^T, scale, softmax        -> fused into kernels/flash_attention
Stage 3: scores x V                   -> (same fused kernel)
Stage 4: concat heads + out projection-> kernels/qmatmul (int8) or jnp

On the FPGA the stages communicate through FIFOs; on TPU stages 2+3 fuse
into one VMEM-resident kernel and stages 1/4 are independent GEMM kernels —
the HBM->VMEM grid pipeline provides the producer/consumer overlap.

This module is the *paper-faithful inference path* used by the serving
engine for quantized models and by the physics-model benchmarks.  The
training path lives in ``models/attention.py`` (differentiable, shardable).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.kernels.flash_attention import mha as fused_attention
from repro.kernels.qmatmul.ops import qmatmul_prequantized


@dataclasses.dataclass
class StreamingMHAParams:
    """Quantized weights for one MHA layer (performance path)."""

    wq: quant.QTensor  # (d_model, n_heads * d_head)
    wk: quant.QTensor
    wv: quant.QTensor
    wo: quant.QTensor  # (n_heads * d_head, d_model)
    bq: jax.Array | None = None
    bk: jax.Array | None = None
    bv: jax.Array | None = None
    bo: jax.Array | None = None


def quantize_mha_params(
    wq, wk, wv, wo, bq=None, bk=None, bv=None, bo=None
) -> StreamingMHAParams:
    return StreamingMHAParams(
        wq=quant.quantize_int8(wq, axis=1),
        wk=quant.quantize_int8(wk, axis=1),
        wv=quant.quantize_int8(wv, axis=1),
        wo=quant.quantize_int8(wo, axis=1),
        bq=bq, bk=bk, bv=bv, bo=bo,
    )


def streaming_mha(
    x: jax.Array,  # (batch, seq, d_model)
    params: StreamingMHAParams,
    *,
    n_heads: int,
    causal: bool = False,
    window: int | None = None,
    softmax_mode: str = "lut",  # the paper's default datapath
    use_pallas_attention: bool = False,
    interpret: bool = False,
) -> jax.Array:
    b, s, d_model = x.shape
    d_head = params.wq.shape[1] // n_heads

    def _proj(inp: jax.Array, w: quant.QTensor, bias) -> jax.Array:
        # Stage 1/4 GEMM: per-row activation quant + prequantized weights.
        flat = inp.reshape(b * s, -1)
        xq = quant.quantize_int8(flat, axis=0)
        out = qmatmul_prequantized(xq, w)
        if bias is not None:
            out = out + bias
        return out

    # ---- Stage 1: linear projections (row-streamed on FPGA) --------------
    q = _proj(x, params.wq, params.bq).reshape(b, s, n_heads, d_head)
    k = _proj(x, params.wk, params.bk).reshape(b, s, n_heads, d_head)
    v = _proj(x, params.wv, params.bv).reshape(b, s, n_heads, d_head)

    # ---- Stages 2+3: fused scores/softmax/weighted-sum -------------------
    q = q.transpose(0, 2, 1, 3)  # (b, h, s, d)
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    o = fused_attention(
        q, k, v,
        causal=causal, window=window, mode=softmax_mode,
        use_pallas=use_pallas_attention, interpret=interpret,
    )

    # ---- Stage 4: concat heads + output projection ------------------------
    o = o.transpose(0, 2, 1, 3).reshape(b, s, n_heads * d_head)
    out = _proj(o, params.wo, params.bo)
    return out.reshape(b, s, -1)


def streaming_mha_float_ref(
    x: jax.Array,
    wq, wk, wv, wo,
    *,
    n_heads: int,
    causal: bool = False,
    window: int | None = None,
) -> jax.Array:
    """Float oracle of the whole 4-stage pipeline (tests/benchmarks)."""
    b, s, _ = x.shape
    d_head = wq.shape[1] // n_heads
    q = (x @ wq).reshape(b, s, n_heads, d_head).transpose(0, 2, 1, 3)
    k = (x @ wk).reshape(b, s, n_heads, d_head).transpose(0, 2, 1, 3)
    v = (x @ wv).reshape(b, s, n_heads, d_head).transpose(0, 2, 1, 3)
    o = fused_attention(q, k, v, causal=causal, window=window, mode="safe",
                        use_pallas=False)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, n_heads * d_head)
    return o @ wo
