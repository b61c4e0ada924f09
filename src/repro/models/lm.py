"""Model orchestrator: causal LMs, encoders, VLMs — scan-over-layers,
prefill/decode with stacked caches, MoE aux accumulation, hybrid
shared-attention, modality-stub frontends.

Entry points
------------
``param_spec / init_params / abstract_params``  — parameter trees
``forward(params, cfg, batch, mode=...)``       — logits (+caches, aux)
``loss_fn``                                     — scalar loss + metrics
``init_caches / abstract_caches``               — stacked KV/SSM caches
``input_specs(cfg, shape)``                     — ShapeDtypeStruct inputs
                                                   for dry-run cells
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import precision as precision_lib
from repro.models import blocks, layers
from repro.models import params as params_lib
from repro.serve import kv_cache as kv_cache_lib

PyTree = Any


# ---------------------------------------------------------------------------
# Parameter trees
# ---------------------------------------------------------------------------


def n_shared_apps(cfg: ModelConfig) -> int:
    if cfg.family != "hybrid":
        return 0
    return math.ceil(cfg.n_layers / cfg.hybrid.attn_every)


def resolve_dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def param_spec(cfg: ModelConfig, dtype=None) -> PyTree:
    dtype = resolve_dtype(cfg) if dtype is None else dtype
    d = cfg.d_model
    spec: dict = {}
    if cfg.frontend != "audio":
        spec["embed"] = layers.embedding_spec(cfg.padded_vocab_size, d, dtype)
    if cfg.frontend is not None:
        fd = cfg.frontend_dim or d
        spec["frontend_proj"] = layers.dense_spec(
            fd, d, axes=("frontend", "embed"), dtype=dtype
        )
    spec["blocks"] = params_lib.stack_spec(
        blocks.block_spec(cfg, dtype), cfg.n_layers
    )
    if cfg.family == "hybrid":
        spec["shared_attn"] = blocks.shared_attn_spec(cfg, dtype)
    spec["final_norm"] = layers.norm_spec(d, cfg.norm_kind, dtype)
    if not cfg.tie_embeddings:
        spec["lm_head"] = layers.dense_spec(
            d, cfg.padded_vocab_size, axes=("embed", "vocab"), dtype=dtype
        )
    return spec


def init_params(cfg: ModelConfig, key: jax.Array, dtype=None) -> PyTree:
    return params_lib.init_params(param_spec(cfg, dtype), key)


def abstract_params(cfg: ModelConfig, dtype=None) -> PyTree:
    return params_lib.abstract_params(param_spec(cfg, dtype))


def count_params(cfg: ModelConfig) -> int:
    return params_lib.count_params(param_spec(cfg))


# ---------------------------------------------------------------------------
# Caches — layout knowledge (dense slabs vs block-table pages, sequence-axis
# maps, logical sharding axes) lives in repro.serve.kv_cache; these aliases
# keep the historical lm-module entry points working.  All three accept the
# layout kwargs (layout= / page_size= / num_pages=) the manager passes.
# ---------------------------------------------------------------------------

abstract_caches = kv_cache_lib.abstract_caches
init_caches = kv_cache_lib.init_caches
cache_logical_axes = kv_cache_lib.cache_logical_axes


# ---------------------------------------------------------------------------
# Embedding frontends
# ---------------------------------------------------------------------------


def _embed_inputs(params, cfg: ModelConfig, batch: dict, mode: str, quant=None):
    """Returns (h, text_offset).  ``batch`` keys by family:

    LM: tokens (b, s).  VLM: patches (b, n_img, fd) + tokens (b, s_text)
    (decode: tokens only).  Audio: frames (b, s, fd).
    """
    qc = cfg.quant if quant is None else quant
    if cfg.frontend == "audio":
        h = layers.dense(params["frontend_proj"], batch["frames"], qc)
        return h, 0
    tok_emb = None
    if "tokens" in batch:
        tok_emb = layers.embed(params["embed"], batch["tokens"]) * cfg.emb_scale
    if cfg.frontend == "patch" and "patches" in batch and mode != "decode":
        patch_emb = layers.dense(
            params["frontend_proj"], batch["patches"], qc
        )
        if tok_emb is not None:
            h = jnp.concatenate([patch_emb, tok_emb], axis=1)
        else:
            h = patch_emb
        return h, patch_emb.shape[1]
    return tok_emb, 0


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _aux_init(cfg: ModelConfig):
    if cfg.moe is None:
        return {}
    return {
        "moe_aux_loss": jnp.float32(0.0),
        "moe_z_loss": jnp.float32(0.0),
        "moe_dropped_frac": jnp.float32(0.0),
    }


def _tree_index(tree, idx):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, idx, 0, keepdims=False), tree
    )


def _tree_update(tree, upd, idx):
    return jax.tree.map(
        lambda a, u: jax.lax.dynamic_update_index_in_dim(
            a, u.astype(a.dtype), idx, 0
        ),
        tree,
        upd,
    )


def _constrain_acts(x: jax.Array, kernel: dict | None) -> jax.Array:
    """Activation sharding constraint at block boundaries.

    Without this, XLA's propagation can resolve the FSDP-weight /
    DP-activation conflict by REPLICATING the batch (observed on the 256-
    chip dry-run: full-batch f32 buffers in the backward while body) —
    the constraint pins activations to (batch over data axes).
    """
    sh = (kernel or {}).get("act_sharding")
    if sh is None:
        return x
    return jax.lax.with_sharding_constraint(x, sh)


def _run_blocks(
    params,
    cfg: ModelConfig,
    h: jax.Array,
    positions: jax.Array,
    *,
    mode: str,
    caches: PyTree | None,
    kernel: dict | None,
    remat: str = "none",
    plan: precision_lib.PrecisionPlan | None = None,
):
    h = _constrain_acts(h, kernel)
    x_embed = h
    layer_caches = caches["layers"] if caches is not None else None
    shared_cache = caches.get("shared") if caches is not None else None
    aux0 = _aux_init(cfg)
    # Position-addressed caches ride the carry whole: each layer writes
    # its tokens into the stacked buffers at its own index, in place.
    # State-sized caches are sliced per layer (scan xs -> ys).
    in_place = kv_cache_lib.rides_layer_carry(layer_caches)
    carried = layer_caches if in_place else None

    plan = plan if plan is not None else precision_lib.resolve_model_plan(cfg)
    # Homogeneous plans keep the legacy single-QuantConfig trace; per-layer
    # heterogeneous plans ride the scan xs as stacked step/bound scalars
    # (one traced body either way — no extra jit programs).
    uniform_quant = plan.uniform_layer_quant()
    shared_quant = plan.shared_quant() if cfg.family == "hybrid" else None
    layer_quants = (
        None if uniform_quant is not None else plan.layer_quant_arrays()
    )

    def body(carry, xs):
        x, shared_c, stacked, aux = carry
        bparams, lcache, idx, *rest = xs
        lquant = rest[0] if rest else uniform_quant
        if in_place:
            lcache = kv_cache_lib.layer_cache(stacked, idx)
        if cfg.family == "hybrid":
            is_attn = (idx % cfg.hybrid.attn_every) == 0
            app_idx = idx // cfg.hybrid.attn_every

            def do_attn(op):
                x_in, sc = op
                c = _tree_index(sc, app_idx) if sc is not None else None
                x_out, new_c = blocks.shared_attn_apply(
                    params["shared_attn"], cfg, x_in, x_embed, positions,
                    mode=mode, cache=c, kernel=kernel, quant=shared_quant,
                )
                sc_out = (
                    _tree_update(sc, new_c, app_idx) if sc is not None else sc
                )
                return x_out, sc_out

            x, shared_c = jax.lax.cond(
                is_attn, do_attn, lambda op: op, (x, shared_c)
            )
        x, new_lcache, l_aux = blocks.block_apply(
            bparams, cfg, x, positions, mode=mode, cache=lcache,
            kernel=kernel, quant=lquant,
        )
        x = _constrain_acts(x, kernel)
        aux = {k: aux[k] + l_aux.get(k, 0.0) for k in aux}
        if in_place:
            stacked, new_lcache = kv_cache_lib.stacked_cache(new_lcache), None
        return (x, shared_c, stacked, aux), new_lcache

    if remat == "full":
        body = jax.checkpoint(body, prevent_cse=False)
    elif remat == "minimal":
        body = jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            prevent_cse=False,
        )

    xs = (
        params["blocks"],
        None if in_place else layer_caches,
        jnp.arange(cfg.n_layers),
    )
    if layer_quants is not None:
        xs = xs + (layer_quants,)
    (x, shared_cache, carried, aux), new_layer_caches = jax.lax.scan(
        body, (h, shared_cache, carried, aux0), xs
    )
    if in_place:
        new_layer_caches = carried
    new_caches = None
    if caches is not None:
        new_caches = {"layers": new_layer_caches}
        if cfg.family == "hybrid":
            new_caches["shared"] = shared_cache
    return x, new_caches, aux


def forward(
    params,
    cfg: ModelConfig,
    batch: dict,
    *,
    mode: str = "train",
    caches: PyTree | None = None,
    positions: jax.Array | None = None,
    kernel: dict | None = None,
    remat: str = "none",
):
    """Returns (logits, new_caches, aux).

    positions: (S,) for train/prefill (defaults to arange), (B,) global
    positions of the new token for decode.
    """
    plan = precision_lib.resolve_model_plan(cfg)
    kernel = plan.kernel_defaults(kernel)
    h, text_offset = _embed_inputs(
        params, cfg, batch, mode, quant=plan.embed_quant()
    )
    if positions is None:
        if mode in ("decode", "extend"):
            raise ValueError(
                f"{mode} requires explicit per-sequence positions"
            )
        positions = jnp.arange(h.shape[1], dtype=jnp.int32)
    x, new_caches, aux = _run_blocks(
        params, cfg, h, positions,
        mode=mode, caches=caches, kernel=kernel, remat=remat, plan=plan,
    )
    x = layers.norm(
        params["final_norm"], x, cfg.norm_kind, cfg.norm_eps,
        use_lut=(kernel or {}).get("norm_lut", False),
    )
    if cfg.tie_embeddings:
        logits = layers.unembed(params["embed"], x)
    else:
        logits = layers.dense(params["lm_head"], x, plan.logits_quant())
    logits = logits * cfg.logit_scale
    # mask vocab padding
    pad = cfg.padded_vocab_size - cfg.vocab_size
    if pad > 0:
        mask = jnp.arange(cfg.padded_vocab_size) < cfg.vocab_size
        logits = jnp.where(mask, logits, -1e9)
    aux["text_offset"] = text_offset
    return logits, new_caches, aux


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def _cross_entropy(logits, labels, mask, tp_safe: bool = False):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    if tp_safe:
        # TP-aware label gather: an einsum against a one-hot partitions
        # cleanly over a vocab-sharded logits axis (becomes a local dot +
        # psum), whereas take_along_axis makes XLA all-gather the logits.
        onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=logp.dtype)
        ll = jnp.einsum("...v,...v->...", logp, onehot)
    else:
        ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    loss = -jnp.sum(ll * mask) / denom
    acc = jnp.sum((jnp.argmax(logits, -1) == labels) * mask) / denom
    return loss, acc


def loss_fn(
    params,
    cfg: ModelConfig,
    batch: dict,
    *,
    kernel: dict | None = None,
    remat: str = "none",
):
    logits, _, aux = forward(
        params, cfg, batch, mode="train", kernel=kernel, remat=remat
    )
    tp_safe = bool((kernel or {}).get("tp_loss", False))
    if cfg.is_encoder:
        labels = batch["labels"]
        mask = batch.get("loss_mask", jnp.ones_like(labels, jnp.float32))
        loss, acc = _cross_entropy(logits, labels, mask, tp_safe)
    else:
        off = aux.pop("text_offset", 0)
        tokens = batch["tokens"]
        text_logits = logits[:, off:]
        pred = text_logits[:, :-1]
        labels = tokens[:, 1:]
        mask = batch.get(
            "loss_mask", jnp.ones_like(tokens, jnp.float32)
        )[:, 1:]
        loss, acc = _cross_entropy(pred, labels, mask, tp_safe)
    total = loss
    metrics = {"ce_loss": loss, "accuracy": acc}
    for k in ("moe_aux_loss", "moe_z_loss"):
        if k in aux:
            total = total + aux[k]
            metrics[k] = aux[k]
    if "moe_dropped_frac" in aux:
        metrics["moe_dropped_frac"] = aux["moe_dropped_frac"] / cfg.n_layers
    metrics["loss"] = total
    return total, metrics


# ---------------------------------------------------------------------------
# Serving entry points
# ---------------------------------------------------------------------------


def prefill(
    params,
    cfg: ModelConfig,
    batch: dict,
    caches: PyTree,
    *,
    kernel: dict | None = None,
):
    """Run the prompt through the model, filling caches.

    Returns (last-position logits (B, V), caches)."""
    logits, new_caches, _ = forward(
        params, cfg, batch, mode="prefill", caches=caches, kernel=kernel
    )
    return logits[:, -1], new_caches


def decode_step(
    params,
    cfg: ModelConfig,
    tokens: jax.Array,  # (B, 1)
    positions: jax.Array,  # (B,) global position of the new token
    caches: PyTree,
    *,
    kernel: dict | None = None,
):
    logits, new_caches, _ = forward(
        params, cfg, {"tokens": tokens}, mode="decode",
        caches=caches, positions=positions, kernel=kernel,
    )
    return logits[:, -1], new_caches


# ---------------------------------------------------------------------------
# Dry-run input specs (ShapeDtypeStruct stand-ins, no allocation)
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """Model inputs for one (arch x shape) dry-run cell."""
    b, s = shape.global_batch, shape.seq_len
    tok = jax.ShapeDtypeStruct((b, s), jnp.int32)
    if shape.kind == "decode":
        return {
            "tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32),
            "positions": jax.ShapeDtypeStruct((b,), jnp.int32),
        }
    if cfg.frontend == "audio":
        fd = cfg.frontend_dim or cfg.d_model
        specs = {"frames": jax.ShapeDtypeStruct((b, s, fd), jnp.float32)}
        if shape.kind == "train":
            specs["labels"] = tok
        return specs
    if cfg.frontend == "patch":
        fd = cfg.frontend_dim or cfg.d_model
        n_img = cfg.n_frontend_tokens
        return {
            "patches": jax.ShapeDtypeStruct((b, n_img, fd), jnp.float32),
            "tokens": jax.ShapeDtypeStruct((b, s - n_img), jnp.int32),
        }
    return {"tokens": tok}
