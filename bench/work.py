"""The work the algorithm requires, counted from shapes, and the least time
the chip needs for it.

Counted so that no implementation can beat it: weights at the dtype the
configuration serves (bf16), the KV of each slot's live context at bf16
and unpadded, the LM head for the last position of each prompt and for
every generated token only, and no token padded to a bucket.  A share of
this least time over a measured device time cannot pass 100% unless the
work is counted too high or the time leaves part of the work out.
"""

from __future__ import annotations

from bench.weights import Dims

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


def decode(m: Dims, positions: list[int], steps: int) -> tuple[float, float]:
    """(flops, bytes) of ``steps`` decode steps that produced tokens at
    ``positions``: weights read once a step, each token's KV read once."""
    flops = sum(token_flops(m, p) + head_flops(m) for p in positions)
    kv = kv_bytes_per_pos(m) * sum(p + 1 for p in positions)
    return float(flops), float(steps * weight_bytes(m) + kv)


def prefill(m: Dims, new_tokens: int, attn_lengths: list[int],
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


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """Roofline time: the larger of compute time and memory time."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
