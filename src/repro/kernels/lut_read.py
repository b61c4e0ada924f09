"""One-hot MXU table reads for Pallas kernel bodies.

A BRAM lookup ``table[idx]`` becomes ``one_hot(idx) @ table`` on the
TPU: a table read that runs on the systolic array.  Mosaic keeps every
vector 2-D with the last axis on lanes and cannot move an index from
lanes to sublanes by reshaping (``(R, C) -> (R * C, 1)`` is refused as an
unsupported shape cast).  So each read builds its one-hot with the
table axis where the index already broadcasts:

* :func:`read_rows` — a ``(R, C)`` index block, read one row at a time:
  ``(1, T) table @ (T, C) one-hot`` puts the table axis on sublanes and
  leaves the index on lanes.
* :func:`read_column` — a ``(R, 1)`` index column (a per-row statistic
  such as a softmax denominator or a variance): ``(R, T) one-hot @
  (T, 1) table`` broadcasts the index along lanes.

Both select exactly one table entry per output at ``HIGHEST`` precision,
so the result is bitwise the gather ``core.lut.lut_lookup`` computes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _dot(a: jax.Array, b: jax.Array) -> jax.Array:
    return jax.lax.dot_general(
        a, b,
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def read_rows(idx_ref, table_row: jax.Array, out_ref) -> None:
    """``out_ref[r, c] = table[idx_ref[r, c]]`` for a 2-D index block.

    ``table_row`` is the table as a ``(1, T)`` row; ``idx_ref`` and
    ``out_ref`` are ``(R, C)`` VMEM refs (int32 and float32)."""
    rows, cols = idx_ref.shape
    size = table_row.shape[-1]

    def body(r, carry):
        idx = idx_ref[pl.ds(r, 1), :]  # (1, C)
        onehot = (
            jax.lax.broadcasted_iota(jnp.int32, (size, cols), 0) == idx
        ).astype(jnp.float32)
        out_ref[pl.ds(r, 1), :] = _dot(table_row, onehot)
        return carry

    jax.lax.fori_loop(0, rows, body, 0)


def read_column(idx: jax.Array, table_col: jax.Array) -> jax.Array:
    """``table[idx]`` for a ``(R, 1)`` int32 index column; ``table_col``
    is the table as a ``(T, 1)`` column.  Returns ``(R, 1)`` float32."""
    size = table_col.shape[0]
    onehot = (
        idx == jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], size), 1)
    ).astype(jnp.float32)
    return _dot(onehot, table_col)
