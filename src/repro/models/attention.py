"""Attention variants: GQA/MHA/MQA, MLA (latent KV), sliding-window — with
prefill/decode KV caches (dense, rolling-buffer, latent, paged,
int8-quantized).

All functions are pure; caches are pytrees (dicts of arrays) so they stack
under scan-over-layers and shard under pjit.  Cache *layout* knowledge
(dense slabs vs block-table pages, sequence-axis maps, specs) lives in
``repro.serve.kv_cache``; decode reads/writes go through that module's
gather/scatter views instead of assuming a contiguous sequence axis.
The fused streaming-attention kernel (``kernels/flash_attention``) is the
TPU target for the score path; the jnp reference path
(``use_pallas=False``) is used on CPU hosts/tests.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import quant
from repro.core import softmax as sm
from repro.kernels.flash_attention import mha as fused_mha
from repro.kernels.flash_attention.ref import NEG_INF
from repro.models import layers
from repro.models.params import ArraySpec
from repro.serve import kv_cache as kv_cache_lib

Cache = dict[str, Any]


# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------


def gqa_spec(cfg: ModelConfig, dtype=jnp.float32):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, hkv = cfg.n_heads, cfg.n_kv_heads
    spec = {
        "wq": layers.dense_spec(d, h * hd, axes=("embed", "heads"), bias=cfg.attn_bias, dtype=dtype),
        "wk": layers.dense_spec(d, hkv * hd, axes=("embed", "kv_heads"), bias=cfg.attn_bias, dtype=dtype),
        "wv": layers.dense_spec(d, hkv * hd, axes=("embed", "kv_heads"), bias=cfg.attn_bias, dtype=dtype),
        "wo": layers.dense_spec(h * hd, d, axes=("heads", "embed"), bias=cfg.attn_bias, dtype=dtype),
    }
    return spec


def mla_spec(cfg: ModelConfig, dtype=jnp.float32):
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq_a": layers.dense_spec(d, m.q_lora_rank, axes=("embed", "q_lora"), dtype=dtype),
        "q_norm": layers.norm_spec(m.q_lora_rank, "rmsnorm", dtype),
        "wq_b": layers.dense_spec(m.q_lora_rank, h * qk, axes=("q_lora", "heads"), dtype=dtype),
        "wkv_a": layers.dense_spec(
            d, m.kv_lora_rank + m.qk_rope_head_dim, axes=("embed", "kv_lora"), dtype=dtype
        ),
        "kv_norm": layers.norm_spec(m.kv_lora_rank, "rmsnorm", dtype),
        "wk_b": layers.dense_spec(
            m.kv_lora_rank, h * m.qk_nope_head_dim, axes=("kv_lora", "heads"), dtype=dtype
        ),
        "wv_b": layers.dense_spec(
            m.kv_lora_rank, h * m.v_head_dim, axes=("kv_lora", "heads"), dtype=dtype
        ),
        "wo": layers.dense_spec(h * m.v_head_dim, d, axes=("heads", "embed"), dtype=dtype),
    }


def attention_spec(cfg: ModelConfig, dtype=jnp.float32):
    if cfg.attn_kind == "mla":
        return mla_spec(cfg, dtype)
    return gqa_spec(cfg, dtype)


# ---------------------------------------------------------------------------
# KV caches
# ---------------------------------------------------------------------------


# Layout-aware cache specs live in repro.serve.kv_cache; these aliases
# keep the historical attention-module entry points working.
cache_spec = kv_cache_lib.attention_cache_spec
init_cache = kv_cache_lib.init_attention_cache


# ---------------------------------------------------------------------------
# GQA apply
# ---------------------------------------------------------------------------


def _split_heads(x, n_heads, head_dim):
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, head_dim).transpose(0, 2, 1, 3)


def _merge_heads(x):
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def gqa_apply(
    params,
    cfg: ModelConfig,
    x: jax.Array,  # (B, S, D)
    positions: jax.Array,  # (S,) global positions
    *,
    mode: str = "train",  # train | prefill | extend | decode
    cache: Cache | None = None,
    kernel: dict | None = None,
    quant=None,  # per-layer runtime hook from the precision plan
) -> tuple[jax.Array, Cache | None]:
    kernel = kernel or {}
    qc = cfg.quant if quant is None else quant
    hd = cfg.resolved_head_dim
    b, s, _ = x.shape

    q = layers.dense(params["wq"], x, qc)
    k = layers.dense(params["wk"], x, qc)
    v = layers.dense(params["wv"], x, qc)
    q = _split_heads(q, cfg.n_heads, hd)
    k = _split_heads(k, cfg.n_kv_heads, hd)
    v = _split_heads(v, cfg.n_kv_heads, hd)
    # positions: (S,) shared across batch for train/prefill, (B,) per-sequence
    # global positions for decode, (B, S) per-row windows for cache-extend
    # (continuous batching).
    if cfg.use_rope:
        if mode == "decode":
            rope_pos = positions[:, None, None]
        elif mode == "extend":
            rope_pos = positions[:, None, :]
        else:
            rope_pos = positions
        q = layers.apply_rope(q, rope_pos, cfg.rope_theta)
        k = layers.apply_rope(k, rope_pos, cfg.rope_theta)

    new_cache = cache
    window = cfg.sliding_window
    rolling = (
        cache is not None
        and window is not None
        and "slot_pos" in (cache or {})
    )
    quantized = cache is not None and "k_scale" in cache

    if quantized:
        k_store, k_sc = _kv_quantize(k)
        v_store, v_sc = _kv_quantize(v)
    elif cache is not None:
        k_store = k.astype(cache["k"].dtype)
        v_store = v.astype(cache["v"].dtype)
        k_sc = v_sc = None

    if mode in ("train",) or cache is None:
        out = fused_mha(
            q, k, v,
            causal=not cfg.is_encoder,
            window=window,
            mode=kernel.get("softmax_mode", "safe"),
            use_pallas=kernel.get("use_pallas", False),
            interpret=kernel.get("interpret", False),
        )
    elif mode == "prefill":
        if kv_cache_lib.is_paged(cache):
            raise ValueError(
                "prefill fills a dense scratch cache; insert it into paged "
                "storage via serve.kv_cache.CacheManager.insert_prefill"
            )
        if rolling:
            w = window

            def tail3(t, fill=0):  # (b,h,s,...) -> last w along axis 2
                if s >= w:
                    return t[:, :, -w:]
                pad = [(0, 0)] * t.ndim
                pad[2] = (0, w - s)
                return jnp.pad(t, pad, constant_values=fill)

            pos_tail = (
                positions[-w:]
                if s >= w
                else jnp.pad(positions, (0, w - s), constant_values=-1)
            )
            # invalid (padded) entries get slot index w -> dropped by scatter
            slots = jnp.where(pos_tail >= 0, pos_tail % w, w)

            def scatter3(buf, t):
                return jnp.zeros_like(buf).at[:, :, slots].set(t, mode="drop")

            new_cache = {
                "k": scatter3(cache["k"], tail3(k_store)),
                "v": scatter3(cache["v"], tail3(v_store)),
            }
            slot_pos = (
                jnp.full((w,), -1, jnp.int32)
                .at[slots]
                .set(pos_tail, mode="drop")
            )
            # per-sequence slot positions (all rows identical after prefill)
            new_cache["slot_pos"] = jnp.broadcast_to(slot_pos, (b, w))
            if quantized:
                new_cache["k_scale"] = scatter3(cache["k_scale"], tail3(k_sc))
                new_cache["v_scale"] = scatter3(cache["v_scale"], tail3(v_sc))
        else:
            new_cache = {
                "k": jax.lax.dynamic_update_slice(
                    cache["k"], k_store, (0, 0, 0, 0)
                ),
                "v": jax.lax.dynamic_update_slice(
                    cache["v"], v_store, (0, 0, 0, 0)
                ),
            }
            if quantized:
                new_cache["k_scale"] = jax.lax.dynamic_update_slice(
                    cache["k_scale"], k_sc, (0, 0, 0)
                )
                new_cache["v_scale"] = jax.lax.dynamic_update_slice(
                    cache["v_scale"], v_sc, (0, 0, 0)
                )
        if quantized:
            # attend the cache's own representation (int8 roundtrip):
            # prefill scores the exact values decode and cache-extend
            # will read back, so replaying any of these positions later
            # reproduces the same bits
            k_att = k_store.astype(jnp.float32) * k_sc[..., None]
            v_att = v_store.astype(jnp.float32) * v_sc[..., None]
        else:
            k_att, v_att = k, v
        out = fused_mha(
            q, k_att, v_att,
            causal=True,
            window=window,
            mode=kernel.get("softmax_mode", "safe"),
            use_pallas=kernel.get("use_pallas", False),
            interpret=kernel.get("interpret", False),
        )
    elif mode == "extend":
        # cache-extending prefill: W window tokens per row written at
        # per-row global positions (B, W) through the layout scatter,
        # then attended with the prefill-path math against the full
        # logical view (history + window) — so the window's activations
        # and cache entries are bitwise what a whole-prompt prefill
        # would have produced at the same positions.  Masked window
        # entries carry an out-of-range sentinel position: dropped by
        # the dense scatter, routed to the trash page by the paged one,
        # and masked out of every window row's reduction.
        if rolling:
            raise ValueError(
                "cache-extend requires a position-addressed cache; "
                "rolling sliding-window buffers prefill exact-length"
            )
        upd = {"k": k_store, "v": v_store}
        if quantized:
            upd["k_scale"], upd["v_scale"] = k_sc, v_sc
        if kv_cache_lib.is_paged(cache):
            new_cache = kv_cache_lib.paged_window_write(cache, upd, positions)
            view = kv_cache_lib.paged_decode_view(new_cache)
        else:
            new_cache = kv_cache_lib.dense_window_write(cache, upd, positions)
            view = new_cache
        kv_pos = jnp.arange(view["k"].shape[2])
        mask = kv_pos[None, None, :] <= positions[:, :, None]  # (B, W, L)
        if window is not None:
            mask = mask & (positions[:, :, None] - kv_pos[None, None, :] < window)
        out = _window_attend(
            q, view["k"], view["v"], mask,
            softmax_mode=kernel.get("softmax_mode", "safe"),
            k_scale=view.get("k_scale"),
            v_scale=view.get("v_scale"),
        )
    else:  # decode: s == 1, attend over cache; positions is (B,) per-seq
        pos = positions  # (B,)
        if kv_cache_lib.is_paged(cache):
            # layout-provided scatter (one token into its physical page)
            # and gather (pages -> contiguous logical view): the math
            # below is then bit-identical to the dense slab path.
            upd = {"k": k_store[:, :, 0], "v": v_store[:, :, 0]}
            if quantized:
                upd["k_scale"] = k_sc[:, :, 0]
                upd["v_scale"] = v_sc[:, :, 0]
            new_cache = kv_cache_lib.paged_decode_write(cache, upd, pos)
            view = kv_cache_lib.paged_decode_view(new_cache)
        else:
            bi = jnp.arange(b)[:, None]
            hi = jnp.arange(cfg.n_kv_heads)[None, :]
            slot = pos % window if rolling else pos  # (B,)
            new_cache = {
                "k": cache["k"].at[bi, hi, slot[:, None]].set(k_store[:, :, 0]),
                "v": cache["v"].at[bi, hi, slot[:, None]].set(v_store[:, :, 0]),
            }
            if quantized:
                new_cache["k_scale"] = cache["k_scale"].at[
                    bi, hi, slot[:, None]
                ].set(k_sc[:, :, 0])
                new_cache["v_scale"] = cache["v_scale"].at[
                    bi, hi, slot[:, None]
                ].set(v_sc[:, :, 0])
            if rolling:
                new_cache["slot_pos"] = cache["slot_pos"].at[
                    jnp.arange(b), slot
                ].set(pos)
            view = new_cache
        if rolling:
            slot_pos = new_cache["slot_pos"]
            valid = (
                (slot_pos >= 0)
                & (slot_pos <= pos[:, None])
                & (slot_pos > pos[:, None] - window)
            )  # (B, w)
        else:
            kv_pos = jnp.arange(view["k"].shape[2])
            valid = kv_pos[None, :] <= pos[:, None]  # (B, L)
        out = _decode_attend(
            q,
            view["k"],
            view["v"],
            valid,
            k_scale=view.get("k_scale"),
            v_scale=view.get("v_scale"),
        )

    out = _merge_heads(out)
    out = layers.dense(params["wo"], out, qc)
    return out, new_cache


def _kv_quantize(x: jax.Array):
    """(b, h, s, d) -> (int8 codes, f32 scales (b, h, s)). Per-token-head
    symmetric int8 — the paper's fixed-point datapath applied to the KV
    cache (4x memory/bandwidth vs bf16)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1), 1e-8) / 127.0
    codes = jnp.clip(jnp.round(x / scale[..., None]), -128, 127).astype(jnp.int8)
    return codes, scale.astype(jnp.float32)


def _window_attend(
    q: jax.Array,  # (B, Hq, W, Dq)
    k: jax.Array,  # (B, Hkv, L, Dk) float or int8 codes
    v: jax.Array,  # (B, Hkv, L, Dv)
    mask: jax.Array,  # (B, W, L) bool: window row i attends kv position j
    *,
    softmax_mode: str = "safe",
    k_scale: jax.Array | None = None,  # (B, Hkv, L) when k is int8
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Window attention over a cache-backed view with *prefill-path*
    math.

    The cache-extending prefill program's attend: a window of W query
    rows against the full logical cache (history + the just-written
    window), under an explicit per-row mask.  Mirrors the jnp reference
    path (``fused_mha`` with ``use_pallas=False`` ->
    ``kernels.flash_attention.ref.attention_ref``) operation for
    operation — KV heads repeated across query groups, one scaled
    einsum, masked scores at NEG_INF (safe) or zero weight (lut) — so
    window rows produce bitwise the activations a whole-prompt prefill
    would have at the same positions.  Masked columns are a suffix of
    the reduction axis and contribute exactly +0.0, which keeps the
    reduction bitwise stable across cache lengths (the same property
    the decode path already relies on).
    """
    b, hq, w, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if k_scale is not None:
        kf = kf * k_scale[..., None]
    if v_scale is not None:
        vf = vf * v_scale[..., None]
    if group > 1:
        kf = jnp.repeat(kf, group, axis=1)
        vf = jnp.repeat(vf, group, axis=1)
    qf = q.astype(jnp.float32)
    scale = 1.0 / (d ** 0.5)
    m = mask[:, None]  # (B, 1, W, L) broadcast over heads
    with jax.named_scope("attnvol"):
        s = jnp.einsum("...qd,...kd->...qk", qf, kf) * scale
        if softmax_mode == "safe":
            s = jnp.where(m, s, NEG_INF)
            p = jax.nn.softmax(s, axis=-1)
        else:  # paper's LUT softmax, masked entries contribute zero weight
            e = sm.lut.lut_exp(s)
            e = jnp.where(m, e, 0.0)
            denom = jnp.sum(e, axis=-1, keepdims=True)
            p = e * sm.lut.lut_inv(denom)
        out = jnp.einsum("...qk,...kd->...qd", p, vf)
    return out.astype(q.dtype)


def _decode_attend(
    q: jax.Array,  # (B, Hq, 1, D)
    k: jax.Array,  # (B, Hkv, L, D) float or int8 codes
    v: jax.Array,
    valid: jax.Array,  # (B, L) bool
    k_scale: jax.Array | None = None,  # (B, Hkv, L) when k is int8
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Single-token attention over a (possibly int8-quantized) cache."""
    b, hq, s, d = q.shape
    assert s == 1, "decode attention expects a single query position"
    hkv = k.shape[1]
    group = hq // hkv
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if k_scale is not None:
        kf = kf * k_scale[..., None]
    if v_scale is not None:
        vf = vf * v_scale[..., None]
    qf = q.astype(jnp.float32).reshape(b, hkv, group * s, d)
    with jax.named_scope("attnvol"):
        scores = jnp.einsum("bhqd,bhkd->bhqk", qf, kf)
        scores = scores / (d ** 0.5)
        scores = jnp.where(valid[:, None, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, vf)
    return out.reshape(b, hq, s, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLA apply
# ---------------------------------------------------------------------------


def mla_apply(
    params,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    *,
    mode: str = "train",
    cache: Cache | None = None,
    kernel: dict | None = None,
    quant=None,  # per-layer runtime hook from the precision plan
    absorb: bool = False,
) -> tuple[jax.Array, Cache | None]:
    """Multi-head latent attention (DeepSeek-V2 / MiniCPM3).

    Paper-faithful baseline materializes per-head K/V from the latent
    (mirrors the FPGA design streaming full K/V); ``absorb=True`` is the
    beyond-paper decode optimization (absorbs wk_b/wv_b into the query/out
    projections so decode attends directly against the latent cache).
    """
    kernel = kernel or {}
    absorb = kernel.get("mla_absorb", absorb)
    m = cfg.mla
    qc = cfg.quant if quant is None else quant
    b, s, _ = x.shape
    h = cfg.n_heads
    nope, rope_d, vd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    qk = nope + rope_d

    if mode == "decode":
        rope_pos = positions[:, None, None]  # (B,) -> (B, 1, 1)
    elif mode == "extend":
        rope_pos = positions[:, None, :]  # (B, W) -> (B, 1, W)
    else:
        rope_pos = positions  # (S,)

    # --- query path ---
    cq = layers.dense(params["wq_a"], x, qc)
    cq = layers.norm(params["q_norm"], cq, "rmsnorm", cfg.norm_eps)
    q = layers.dense(params["wq_b"], cq, qc).reshape(b, s, h, qk)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = layers.apply_rope(
        q_rope.transpose(0, 2, 1, 3), rope_pos, cfg.rope_theta
    )  # (b, h, s, rope_d)
    q_nope = q_nope.transpose(0, 2, 1, 3)  # (b, h, s, nope)

    # --- latent KV path ---
    kv_a = layers.dense(params["wkv_a"], x, qc)
    ckv, k_rope = kv_a[..., : m.kv_lora_rank], kv_a[..., m.kv_lora_rank :]
    ckv = layers.norm(params["kv_norm"], ckv, "rmsnorm", cfg.norm_eps)
    k_rope = layers.apply_rope(
        k_rope[:, None], rope_pos, cfg.rope_theta
    )[:, 0]  # (b, s, rope_d), shared across heads

    latent = jnp.concatenate([ckv, k_rope], axis=-1)  # (b, s, r + rope_d)

    new_cache = cache
    quantized = cache is not None and "latent_scale" in cache
    if cache is not None:
        cache_dtype = cache["latent"].dtype
        if quantized:
            # per-token symmetric int8 over the latent width
            l_scale = jnp.maximum(jnp.max(jnp.abs(latent), axis=-1), 1e-8) / 127.0
            l_store = jnp.clip(
                jnp.round(latent / l_scale[..., None]), -128, 127
            ).astype(jnp.int8)
        else:
            l_store, l_scale = latent.astype(cache_dtype), None
        if mode == "prefill":
            if kv_cache_lib.is_paged(cache):
                raise ValueError(
                    "prefill fills a dense scratch cache; insert it into "
                    "paged storage via serve.kv_cache.CacheManager"
                    ".insert_prefill"
                )
            new_latent = jax.lax.dynamic_update_slice(
                cache["latent"], l_store, (0, 0, 0)
            )
            new_cache = {"latent": new_latent}
            if quantized:
                new_cache["latent_scale"] = jax.lax.dynamic_update_slice(
                    cache["latent_scale"], l_scale.astype(jnp.float32), (0, 0)
                )
        elif mode == "extend":  # window scatter at (B, W) positions
            upd = {"latent": l_store}
            if quantized:
                upd["latent_scale"] = l_scale.astype(jnp.float32)
            write = (
                kv_cache_lib.paged_window_write
                if kv_cache_lib.is_paged(cache)
                else kv_cache_lib.dense_window_write
            )
            new_cache = write(cache, upd, positions)
        elif kv_cache_lib.is_paged(cache):  # paged decode: page scatter
            upd = {"latent": l_store[:, 0]}
            if quantized:
                upd["latent_scale"] = l_scale[:, 0].astype(jnp.float32)
            new_cache = kv_cache_lib.paged_decode_write(cache, upd, positions)
        else:  # dense decode: positions is (B,)
            new_latent = cache["latent"].at[jnp.arange(b), positions].set(
                l_store[:, 0]
            )
            new_cache = {"latent": new_latent}
            if quantized:
                new_cache["latent_scale"] = cache["latent_scale"].at[
                    jnp.arange(b), positions
                ].set(l_scale[:, 0].astype(jnp.float32))

    if mode == "decode" and cache is not None:
        pos = positions  # (B,)
        view = (
            kv_cache_lib.paged_decode_view(new_cache)
            if kv_cache_lib.is_paged(new_cache)
            else new_cache
        )
        lat = view["latent"].astype(jnp.float32)  # (b, L, r+rope_d)
        if quantized:
            lat = lat * view["latent_scale"][..., None]
        ckv_all, krope_all = lat[..., : m.kv_lora_rank], lat[..., m.kv_lora_rank :]
        valid = jnp.arange(lat.shape[1])[None, :] <= pos[:, None]  # (B, L)
        scale = 1.0 / (qk ** 0.5)
        if absorb:
            # beyond-paper: q_nope' = q_nope @ wk_b^T (per head) -> latent dim
            wk_b = params["wk_b"]["kernel"].reshape(m.kv_lora_rank, h, nope)
            q_lat = jnp.einsum("bhsn,rhn->bhsr", q_nope, wk_b)
            with jax.named_scope("attnvol"):
                scores = (
                    jnp.einsum("bhsr,bLr->bhsL", q_lat, ckv_all)
                    + jnp.einsum("bhsd,bLd->bhsL", q_rope, krope_all)
                ) * scale
                scores = jnp.where(valid[:, None, None, :], scores, -1e30)
                probs = jax.nn.softmax(scores, axis=-1)
                o_lat = jnp.einsum("bhsL,bLr->bhsr", probs, ckv_all)
            wv_b = params["wv_b"]["kernel"].reshape(m.kv_lora_rank, h, vd)
            out = jnp.einsum("bhsr,rhv->bhsv", o_lat, wv_b)
        else:
            # paper-faithful: materialize per-head K/V from the latent
            k_nope = layers.dense(params["wk_b"], ckv_all, qc).reshape(
                b, -1, h, nope
            )
            vv = layers.dense(params["wv_b"], ckv_all, qc).reshape(b, -1, h, vd)
            with jax.named_scope("attnvol"):
                scores = (
                    jnp.einsum("bhsn,bLhn->bhsL", q_nope, k_nope)
                    + jnp.einsum("bhsd,bLd->bhsL", q_rope, krope_all)
                ) * scale
                scores = jnp.where(valid[:, None, None, :], scores, -1e30)
                probs = jax.nn.softmax(scores, axis=-1)
                out = jnp.einsum("bhsL,bLhv->bhsv", probs, vv)
        out = out.transpose(0, 2, 1, 3).reshape(b, s, h * vd)
        out = out.astype(x.dtype)  # decode math runs f32; restore carry dtype
    elif mode == "extend" and cache is not None:
        # cache-extending prefill: attend the window rows with the
        # prefill-path math against the full latent view (history + the
        # just-written window), materializing per-head K/V from the
        # latent exactly as the whole-prompt prefill does — so window
        # activations and cache entries are bitwise what that prefill
        # would have produced at the same positions.
        view = (
            kv_cache_lib.paged_decode_view(new_cache)
            if kv_cache_lib.is_paged(new_cache)
            else new_cache
        )
        lat = view["latent"].astype(jnp.float32)  # (b, L, r+rope_d)
        if quantized:
            lat = lat * view["latent_scale"][..., None]
        ckv_all = lat[..., : m.kv_lora_rank]
        krope_all = lat[..., m.kv_lora_rank :]
        L = lat.shape[1]
        k_nope = layers.dense(params["wk_b"], ckv_all, qc).reshape(
            b, L, h, nope
        )
        vv = layers.dense(params["wv_b"], ckv_all, qc).reshape(b, L, h, vd)
        k_full = jnp.concatenate(
            [
                k_nope.transpose(0, 2, 1, 3),
                jnp.broadcast_to(krope_all[:, None], (b, h, L, rope_d)),
            ],
            axis=-1,
        )
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)  # (b, h, W, qk)
        kv_pos = jnp.arange(L)
        mask = kv_pos[None, None, :] <= positions[:, :, None]  # (B, W, L)
        out = _window_attend(
            q_full, k_full, vv.transpose(0, 2, 1, 3), mask,
            softmax_mode=kernel.get("softmax_mode", "safe"),
        )
        out = _merge_heads(out)
    else:
        # train / prefill: materialize K/V (paper-faithful streaming form)
        if quantized and mode == "prefill":
            # attend the cache's own representation (int8 roundtrip), so
            # replaying these positions via decode or cache-extend reads
            # back exactly the values prefill scored
            lat_att = l_store.astype(jnp.float32) * l_scale[..., None]
            ckv_att = lat_att[..., : m.kv_lora_rank]
            krope_att = lat_att[..., m.kv_lora_rank :]
        else:
            ckv_att, krope_att = ckv, k_rope
        k_nope = layers.dense(params["wk_b"], ckv_att, qc).reshape(b, s, h, nope)
        vv = layers.dense(params["wv_b"], ckv_att, qc).reshape(b, s, h, vd)
        k_full = jnp.concatenate(
            [
                k_nope.transpose(0, 2, 1, 3),
                jnp.broadcast_to(krope_att[:, None], (b, h, s, rope_d)),
            ],
            axis=-1,
        )
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        # pad v head dim up to qk dim for the fused kernel, slice after
        v_heads = vv.transpose(0, 2, 1, 3)
        pad = qk - vd
        if pad > 0:
            v_heads = jnp.pad(v_heads, ((0, 0), (0, 0), (0, 0), (0, pad)))
        out = fused_mha(
            q_full, k_full, v_heads,
            causal=not cfg.is_encoder,
            mode=kernel.get("softmax_mode", "safe"),
            use_pallas=kernel.get("use_pallas", False),
            interpret=kernel.get("interpret", False),
        )[..., :vd]
        out = _merge_heads(out)

    out = layers.dense(params["wo"], out, qc)
    return out, new_cache


def attention_apply(params, cfg, x, positions, **kw):
    if cfg.attn_kind == "mla":
        return mla_apply(params, cfg, x, positions, **kw)
    return gqa_apply(params, cfg, x, positions, **kw)
