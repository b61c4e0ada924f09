"""Plain float32 reference of the llama-style decoder that both benchmark
configurations are (MiniCPM's muP scalings included).

The forward pass over whole sequences, written out in ``jax.numpy`` with
every matrix product at HIGHEST precision: embedding times ``emb_scale``;
per layer, RMSNorm, causal multi-head attention with rotary positions
(rotate-half convention) and KV heads repeated over their query groups,
the output projection added at ``residual_scale``, RMSNorm, a SwiGLU MLP
added at ``residual_scale``; a final RMSNorm and the tied embedding as the
LM head, times ``logit_scale``.  No cache, paging, bucketing, kernel or
code of the program under test.  Weights are upcast from their stored
dtype layer by layer, so the reference fits beside them on one chip.

The same code computes the check's lower-precision control (``quant``):
``"w8"`` rounds every weight matrix to symmetric int8 per output channel
(the embedding per row), ``"w8a8"`` also rounds each token's input to
every such product to int8.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import LAYER_KEYS, Dims

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


def hidden_states(m: Dims, w: dict, tokens: np.ndarray, quant=None):
    """Final hidden states (n, s, d) of token rows (n, s), float32."""
    h = _embed(m, w["embed"], jnp.asarray(tokens), quant)
    for i in range(m.layers):
        h = _layer(m, h, {k: w[k] for k in LAYER_KEYS}, i, quant)
    return h


def logits(m: Dims, w: dict, tokens: np.ndarray) -> np.ndarray:
    """Every position's logits (n, s, vocab), for small sizes."""
    h = hidden_states(m, w, tokens)
    x = _rms(h, w["final_norm"].astype(F32), m.norm_eps)
    return np.asarray(_mm("nsd,vd->nsv", x, _table(w["embed"], m, None)) * m.logit_scale)


def logit_gaps(m: Dims, w: dict, seqs: list[tuple[list[int], list[int]]],
               length: int, quant: str | None = None) -> list[np.ndarray]:
    """For each (prompt, served) pair: by how much each served token's
    reference logit lies below the reference's best at its position, the
    reference run once over prompt + served tokens padded to ``length``.
    With ``quant``, the control's gaps instead: at the same positions of
    the same tokens, the gap of the token the control puts first."""
    out = []
    for start in range(0, len(seqs), ROWS):
        chunk = seqs[start:start + ROWS]
        toks = np.zeros((ROWS, length), np.int32)
        for r, (prompt, served) in enumerate(chunk):
            seq = list(prompt) + list(served)
            toks[r, :len(seq)] = seq
        h = hidden_states(m, w, toks)
        c = hidden_states(m, w, toks, quant) if quant else h
        for r, (prompt, served) in enumerate(chunk):
            nxt = jnp.asarray(np.roll(toks[r], -1))
            gap = _head(m, h[r], c[r], w["embed"], w["final_norm"], nxt, quant)
            lo, hi = len(prompt) - 1, len(prompt) + len(served) - 1
            out.append(np.asarray(gap)[lo:hi])
    return out
