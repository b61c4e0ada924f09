"""The llama-style decoder: RMSNorm, causal grouped-query attention with
rotary positions, a SwiGLU MLP and a tied embedding, with MiniCPM's muP
scalings where the configuration file has them (``scale_emb``,
``scale_depth``, ``dim_model_base``).

Everything of the benchmark that depends on this architecture: the
engine's ``ModelConfig``, the weights' shapes and seeded draw, the
engine's parameter tree, the plain float32 reference with its
lower-precision control, and the work the algorithm requires.

The reference is the forward pass over whole sequences, written out in
``jax.numpy`` with every matrix product at HIGHEST precision: embedding
times ``emb_scale``; per layer, RMSNorm, causal multi-head attention with
rotary positions (rotate-half convention) and KV heads repeated over their
query groups, the output projection added at ``residual_scale``, RMSNorm,
a SwiGLU MLP added at ``residual_scale``; a final RMSNorm and the tied
embedding as the LM head, times ``logit_scale``.  No cache, paging,
bucketing, kernel or code of the program under test.  Weights are upcast
from their stored dtype layer by layer, so the reference fits beside them.

The same code computes the check's lower-precision control (``quant``):
``"w8"`` rounds every weight matrix to symmetric int8 per output channel
(the embedding per row), ``"w8a8"`` also rounds each token's input to
every such product to int8.

The work counts are made so that no implementation can beat them: weights
at the dtype the configuration serves (bf16), the KV of each slot's live
context at bf16 and unpadded, the LM head for the last position of each
prompt and for every generated token only, and no token padded to a
bucket.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import Place


class Dims(NamedTuple):
    """Shapes and scalars of one configuration (hashable, so it can be a
    static argument of a jitted function)."""

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


def model_config(cfg: dict):
    """The engine's ModelConfig for a configuration file."""
    from repro.configs.base import ModelConfig

    m = cfg["model"]
    if m["hidden_act"] != "silu" or not m["tie_word_embeddings"]:
        raise ValueError("llama configurations are SwiGLU decoders with tied embeddings")
    d, layers = m["hidden_size"], m["num_hidden_layers"]
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=layers, d_model=d,
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        attn_kind="gqa", norm_kind="rmsnorm", act="silu", gated_mlp=True,
        rope_theta=float(m["rope_theta"]), tie_embeddings=True,
        emb_scale=float(m.get("scale_emb", 1.0)),
        residual_scale=float(m.get("scale_depth", math.sqrt(layers)) / math.sqrt(layers)),
        logit_scale=float(m.get("dim_model_base", d)) / d,
        norm_eps=float(m["rms_norm_eps"]), dtype=m["torch_dtype"],
    )


def dims(mc) -> Dims:
    return Dims(mc.n_layers, mc.d_model, mc.n_heads, mc.n_kv_heads,
                mc.resolved_head_dim, mc.d_ff, mc.vocab_size,
                mc.padded_vocab_size, mc.emb_scale, mc.residual_scale,
                mc.logit_scale, mc.norm_eps, mc.rope_theta)


# -------------------------------------------------------------- weights --

#: per-layer leaves, stacked on a leading layer axis
LAYER_KEYS = ("ln1", "wq", "wk", "wv", "wo", "ln2", "w_gate", "w_up", "w_down")
NORM_KEYS = ("ln1", "ln2", "final_norm")
#: the model-width (hidden_size) axis of each leaf: on a cell of several
#: chips each chip holds its slice of every leaf along it
SPLIT_AXIS = {"embed": 1, "ln1": 1, "ln2": 1, "final_norm": 0, "wq": 1, "wk": 1,
              "wv": 1, "wo": 2, "w_gate": 1, "w_up": 1, "w_down": 2}


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


def draw(m: Dims, key: jax.Array, embed_std: float, dtype: str) -> dict:
    """Embedding rows at ``embed_std``, projections at 1/sqrt(fan-in), norm
    scales 1."""
    dt = jnp.dtype(dtype)
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(m).items())):
        if name in NORM_KEYS:
            out[name] = jnp.ones(shape, dt)
            continue
        std = embed_std if name == "embed" else shape[-2] ** -0.5
        d = jax.random.normal(jax.random.fold_in(key, i), shape, dt)
        out[name] = d * jnp.asarray(std, dt)
    return out


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


# ------------------------------------------------------------ reference --

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
#: sequences per reference batch (padded with empty rows)
ROWS = 8


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x (n, heads, s, hd) at positions 0..s-1."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(x.shape[2], dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _int8(a, axis):
    """Round to symmetric int8 with one scale per slice along ``axis``."""
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(a / scale) * scale


def _weight(a, quant):
    """A (..., in, out) matrix as the reference (or its control) uses it."""
    a = a.astype(F32)
    return _int8(a, -2) if quant else a


def _act(x, quant):
    return _int8(x, -1) if quant == "w8a8" else x


def _table(table, m: Dims, quant):
    t = table[: m.vocab].astype(F32)
    return _int8(t, -1) if quant else t


@functools.partial(jax.jit, static_argnums=(0, 3))
def _embed(m: Dims, table, tokens, quant=None):
    return _table(table, m, quant)[tokens] * m.emb_scale


@functools.partial(jax.jit, static_argnums=(0, 4))
def _layer(m: Dims, h, w, i, quant=None):
    lw = {k: jax.lax.dynamic_index_in_dim(w[k], i, 0, False) for k in LAYER_KEYS}
    n, s, _ = h.shape
    H, G, hd = m.heads, m.heads // m.kv_heads, m.head_dim

    def lin(spec, x, name):
        return _mm(spec, _act(x, quant), _weight(lw[name], quant))

    def heads(x, count):
        return x.reshape(n, s, count, hd).transpose(0, 2, 1, 3)

    a = _rms(h, lw["ln1"].astype(F32), m.norm_eps)
    q = _rope(heads(lin("nsd,de->nse", a, "wq"), H), m.rope_theta)
    k = _rope(heads(lin("nsd,de->nse", a, "wk"), m.kv_heads), m.rope_theta)
    v = heads(lin("nsd,de->nse", a, "wv"), m.kv_heads)
    k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
    scores = _mm("nhqd,nhkd->nhqk", q, k) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = _mm("nhqk,nhkd->nhqd", p, v).transpose(0, 2, 1, 3).reshape(n, s, H * hd)
    h = h + m.residual_scale * lin("nse,ed->nsd", o, "wo")
    a = _rms(h, lw["ln2"].astype(F32), m.norm_eps)
    gate = jax.nn.silu(lin("nsd,df->nsf", a, "w_gate"))
    up = lin("nsd,df->nsf", a, "w_up")
    return h + m.residual_scale * lin("nsf,fd->nsd", gate * up, "w_down")


def _logits(m: Dims, h_row, table, final_norm, quant=None):
    x = _act(_rms(h_row, final_norm.astype(F32), m.norm_eps), quant)
    return _mm("sd,vd->sv", x, _table(table, m, quant)) * m.logit_scale


@functools.partial(jax.jit, static_argnums=(0, 6))
def _head(m: Dims, h_row, c_row, table, final_norm, nxt, quant=None):
    """Per position: the reference's best logit and its logit of the token
    that follows in the sequence, or with ``quant`` of the token the
    control (hidden states ``c_row``) puts first."""
    logits = _logits(m, h_row, table, final_norm)
    if quant:
        nxt = jnp.argmax(_logits(m, c_row, table, final_norm, quant), axis=1)
    served = jnp.take_along_axis(logits, nxt[:, None], axis=1)[:, 0]
    return logits.max(axis=1) - served


def hidden_states(m: Dims, w: dict, tokens: np.ndarray, quant=None,
                  place: Place | None = None):
    """Final hidden states (n, s, d) of token rows (n, s), float32, one
    layer at a time on ``place`` (one device where None)."""
    place = place or Place()
    h = _embed(m, place.whole(w["embed"]), jnp.asarray(tokens), quant)
    stacked = {k: w[k] for k in LAYER_KEYS}
    for i in range(m.layers):
        h = _layer(m, h, *place.layer(stacked, i), quant)
    return h


def logits(m: Dims, w: dict, tokens: np.ndarray, place: Place | None = None) -> np.ndarray:
    """Every position's logits (n, s, vocab), for small sizes."""
    place = place or Place()
    h = hidden_states(m, w, tokens, place=place)
    x = _rms(h, place.whole(w["final_norm"]).astype(F32), m.norm_eps)
    return np.asarray(_mm("nsd,vd->nsv", x, _table(place.whole(w["embed"]), m, None))
                      * m.logit_scale)


def logit_gaps(m: Dims, w: dict, seqs: list[tuple[list[int], list[int]]],
               length: int, quant: str | None = None,
               place: Place | None = None) -> list[np.ndarray]:
    """For each (prompt, served) pair: by how much each served token's
    reference logit lies below the reference's best at its position, the
    reference run once over prompt + served tokens padded to ``length``.
    With ``quant``, the control's gaps instead: at the same positions of
    the same tokens, the gap of the token the control puts first."""
    place = place or Place()
    table, final_norm = place.whole(w["embed"]), place.whole(w["final_norm"])
    out = []
    for start in range(0, len(seqs), ROWS):
        chunk = seqs[start:start + ROWS]
        toks = np.zeros((ROWS, length), np.int32)
        for r, (prompt, served) in enumerate(chunk):
            seq = list(prompt) + list(served)
            toks[r, :len(seq)] = seq
        h = hidden_states(m, w, toks, place=place)
        c = hidden_states(m, w, toks, quant, place) if quant else h
        for r, (prompt, served) in enumerate(chunk):
            nxt = jnp.asarray(np.roll(toks[r], -1))
            gap = _head(m, h[r], c[r], table, final_norm, nxt, quant)
            lo, hi = len(prompt) - 1, len(prompt) + len(served) - 1
            out.append(np.asarray(gap)[lo:hi])
    return out


# ----------------------------------------------------------------- work --

BF16 = 2


def linear_params(m: Dims) -> int:
    """Matrix entries one token multiplies through in the decoder layers."""
    hd = m.head_dim
    attn = m.d_model * hd * (2 * m.heads + 2 * m.kv_heads)
    return m.layers * (attn + 3 * m.d_model * m.d_ff)


def weight_bytes(m: Dims) -> int:
    """Bytes one forward step reads: every layer and the LM head (the tied
    embedding, unpadded vocabulary)."""
    norms = (2 * m.layers + 1) * m.d_model
    return BF16 * (linear_params(m) + m.vocab * m.d_model + norms)


def kv_bytes_per_pos(m: Dims) -> int:
    return BF16 * 2 * m.layers * m.kv_heads * m.head_dim


def attn_flops(m: Dims, keys: int) -> int:
    """Scores and weighted values for one query against ``keys`` keys."""
    return 4 * m.layers * m.heads * m.head_dim * keys


def head_flops(m: Dims) -> int:
    return 2 * m.d_model * m.vocab


def token_flops(m: Dims, pos: int) -> int:
    """A token at position ``pos`` through the layers (no LM head)."""
    return 2 * linear_params(m) + attn_flops(m, pos + 1)


def decode_work(m: Dims, positions: list[int], steps: int) -> tuple[float, float]:
    """(flops, bytes) of ``steps`` decode steps that produced tokens at
    ``positions``: weights read once a step, each token's KV read once."""
    flops = sum(token_flops(m, p) + head_flops(m) for p in positions)
    kv = kv_bytes_per_pos(m) * sum(p + 1 for p in positions)
    return float(flops), float(steps * weight_bytes(m) + kv)


def prefill_work(m: Dims, new_tokens: int, attn_lengths: list[int],
                 dispatches: int) -> tuple[float, float]:
    """(flops, bytes) of prompts that put ``new_tokens`` tokens, all told,
    through the layers (those the prefix cache did not serve).  Attention
    is counted for prompts of ``attn_lengths`` tokens each, as if each
    started its sequence (a lower bound where a prefix was served), the LM
    head once a prompt, weights read once a dispatch, KV written once."""
    # query i of a prompt attends i + 1 keys: n (n + 1) / 2 keys a prompt
    flops = new_tokens * 2 * linear_params(m) + sum(
        attn_flops(m, n * (n + 1) // 2) + head_flops(m) for n in attn_lengths
    )
    kv = kv_bytes_per_pos(m) * new_tokens
    return float(flops), float(dispatches * weight_bytes(m) + kv)
