"""Seeded weights for a llama-style decoder, made on the device in one jitted
call and in the dtype the configuration serves.

The benchmark owns the draw: the engine under test and the plain reference
are both handed what :func:`make` returns for a seed, so the reference takes
no weights from the program.  :func:`program_tree` only rearranges the
arrays into the parameter tree the engine reads.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Dims(NamedTuple):
    """Shapes and scalars of one llama-style configuration (hashable, so it
    can be a static argument of a jitted function)."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    padded_vocab: int
    emb_scale: float
    residual_scale: float
    logit_scale: float
    norm_eps: float
    rope_theta: float


#: per-layer leaves, stacked on a leading layer axis
LAYER_KEYS = ("ln1", "wq", "wk", "wv", "wo", "ln2", "w_gate", "w_up", "w_down")
NORM_KEYS = ("ln1", "ln2", "final_norm")


def shapes(m: Dims) -> dict[str, tuple[int, ...]]:
    L, d, hd = m.layers, m.d_model, m.head_dim
    return {
        "embed": (m.padded_vocab, d),
        "ln1": (L, d),
        "wq": (L, d, m.heads * hd),
        "wk": (L, d, m.kv_heads * hd),
        "wv": (L, d, m.kv_heads * hd),
        "wo": (L, m.heads * hd, d),
        "ln2": (L, d),
        "w_gate": (L, d, m.d_ff),
        "w_up": (L, d, m.d_ff),
        "w_down": (L, m.d_ff, d),
        "final_norm": (d,),
    }


def prng_key(seed: int) -> jax.Array:
    """A JAX key from any non-negative seed, also one wider than 32 bits."""
    word = int(np.random.SeedSequence(int(seed)).generate_state(1)[0])
    return jax.random.PRNGKey(word & 0x7FFFFFFF)


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _draw(m: Dims, key: jax.Array, embed_std: float, dtype: str) -> dict:
    dt = jnp.dtype(dtype)
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(m).items())):
        if name in NORM_KEYS:
            out[name] = jnp.ones(shape, dt)
            continue
        std = embed_std if name == "embed" else shape[-2] ** -0.5
        draw = jax.random.normal(jax.random.fold_in(key, i), shape, dt)
        out[name] = draw * jnp.asarray(std, dt)
    return out


def make(m: Dims, seed: int, embed_std: float, dtype: str = "bfloat16") -> dict:
    """Every weight of the configuration, drawn from ``seed``: embedding
    rows at ``embed_std``, projections at 1/sqrt(fan-in), norm scales 1."""
    return jax.block_until_ready(_draw(m, prng_key(seed), float(embed_std), dtype))


def program_tree(w: dict) -> dict:
    """The engine's parameter tree over the same arrays (no copy)."""
    kern = lambda a: {"kernel": a}  # noqa: E731
    return {
        "embed": {"table": w["embed"]},
        "blocks": {
            "ln1": {"scale": w["ln1"]},
            "attn": {k: kern(w[k]) for k in ("wq", "wk", "wv", "wo")},
            "ln2": {"scale": w["ln2"]},
            "ffn": {k: kern(w[k]) for k in ("w_gate", "w_up", "w_down")},
        },
        "final_norm": {"scale": w["final_norm"]},
    }
