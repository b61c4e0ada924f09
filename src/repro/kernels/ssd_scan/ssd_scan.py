"""Mamba2 SSD chunked scan as a Pallas TPU kernel (arXiv:2405.21060).

The SSD duality splits the selective-state recurrence into an intra-chunk
quadratic part (two MXU matmuls masked by the decay matrix L) and an
inter-chunk linear state pass — structurally the same producer/consumer
pipeline as the paper's streaming MHA: chunk tensors stream HBM->VMEM
while the (P, N) running state lives in VMEM scratch across the
sequential chunk dimension (the FIFO/persistent-register analogue).

Grid: ``(batch*heads, n_chunks)`` — heads parallel, chunks sequential.
MXU-friendly construction: the in-chunk cumulative sums are computed as a
lower-triangular-ones matmul (``tril @ a``) instead of a scan, so every
heavy op is a dot.

VMEM working set per step: q*(P + 2N) inputs + q^2 decay/score tiles +
(P, N) state — for the assigned configs (q=64, P=64, N<=128) well under
1 MiB, leaving the double-buffered pipeline full depth.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_chunk_kernel(xdt_ref, a_ref, b_ref, c_ref, y_ref, state_ref):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    xdt = xdt_ref[0].astype(jnp.float32)  # (q, p)
    a = a_ref[0].astype(jnp.float32)  # (q, 1) log-decay per step
    bm = b_ref[0].astype(jnp.float32)  # (q, n)
    cm = c_ref[0].astype(jnp.float32)  # (q, n)
    q = xdt.shape[0]

    # inclusive cumulative sum via lower-tri ones matmul (MXU, not scan)
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tril_incl = (ii >= jj).astype(jnp.float32)
    cs = jax.lax.dot_general(
        tril_incl, a, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (q, 1), cs_i = sum_{m<=i} a_m

    # decay matrix L[i,j] = exp(cs_i - cs_j) for i >= j (sum over j+1..i)
    seg = cs - cs.reshape(1, q)  # [i, j] = cs_i - cs_j
    el = jnp.where(ii >= jj, jnp.exp(seg), 0.0)

    # intra-chunk: (C B^T ⊙ L) @ xdt
    scores = jax.lax.dot_general(
        cm, bm, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    y = jax.lax.dot_general(
        scores * el, xdt, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    # inter-chunk: contribution of the carried state, decayed into chunk
    state = state_ref[...]  # (p, n)
    y_off = jax.lax.dot_general(
        cm, state, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (q, p)
    y = y + y_off * jnp.exp(cs)

    # state update: decay to chunk end + sum of B-weighted inputs
    decay_to_end = jnp.exp(cs[-1] - cs)  # (q, 1)
    upd = jax.lax.dot_general(
        xdt * decay_to_end, bm, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (p, n)
    state_ref[...] = state * jnp.exp(cs[-1]) + upd
    y_ref[0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(
    xdt: jax.Array,  # (BH, L, P) inputs pre-multiplied by dt
    a: jax.Array,  # (BH, L, 1) log-decay
    bmat: jax.Array,  # (BH, L, N)
    cmat: jax.Array,  # (BH, L, N)
    *,
    chunk: int = 64,
    interpret: bool = False,
) -> jax.Array:
    bh, l, p = xdt.shape
    n = bmat.shape[-1]
    assert l % chunk == 0, (l, chunk)
    grid = (bh, l // chunk)
    return pl.pallas_call(
        _ssd_chunk_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, p), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, l, p), xdt.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="ssd_chunked_scan",
    )(xdt, a, bmat, cmat)
