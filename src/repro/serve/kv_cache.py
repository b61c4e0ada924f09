"""KV-cache subsystem: one CacheManager, two storage layouts (dense, paged).

The paper's sub-2us datapath works because the memory layout is decided
once, ahead of time, and every pipeline stage addresses it with fixed
strides.  This module gives the serving engine the same discipline at
datacenter scale: all KV-cache *layout* knowledge — which leaves have a
sequence axis, how a prefilled slab is inserted into a slot, how decode
reads and writes one token — lives here, behind a small set of traced
helpers plus a host-side :class:`CacheManager`.

Two layouts share one interface:

* **dense** — the classic per-slot slab: every cache leaf carries a
  ``(batch, ..., max_seq_len, ...)`` sequence axis and slot ``i`` owns
  row ``i`` for the engine's lifetime.  Bit-identical to the historical
  engine behavior.

* **paged** — block-table-indexed pages (vLLM-style, with hls4ml's
  fixed-stride flavor: ``max_seq_len`` must be a whole number of pages).
  K/V live in a shared pool ``(num_pages, ..., page_size, ...)`` with no
  batch axis; each slot holds a ``page_table`` row of physical page ids.
  Long contexts allocate pages on demand as decode crosses page
  boundaries, and a finished slot returns its pages to the free list
  immediately.  Admission *reserves* each request's worst-case page
  count (prompt + generation budget) up front — allocation stays lazy,
  but decode growth can never exhaust the pool mid-run; when the pool
  cannot cover the queue head's reservation, admission waits FIFO until
  finished slots return pages.  Physical page 0 is a reserved *trash*
  page: unallocated table entries point at it, so masked pad writes land
  there harmlessly and are never read back (reads are masked by position
  validity).

The attention layer does not assume a contiguous sequence axis: it asks
``is_paged(cache)`` and goes through :func:`paged_decode_write` /
:func:`paged_decode_view` (gather/scatter views) when the per-layer
cache is a page pool.  The pool is updated in place: position-addressed
caches ride the model's layer loop whole (:func:`rides_layer_carry`),
each layer handed the stacked cache plus its index
(:func:`layer_cache`), so its token writes land straight in the stacked
buffers and its reads gather straight from them; the engine's programs
donate the caches they are handed.  Prefill always fills a *dense*
scratch cache (the model's natural contiguous write), and the engine's
jitted prefill program inserts it through
:meth:`CacheManager.insert_prefill`, which is the only layout-specific
step.

Families whose state is not position-addressed (SSM/hybrid state,
rolling sliding-window buffers) cannot be paged; the manager silently
falls back to dense for them, mirroring the engine's exact-length
prefill fallback.

int8 KV policies compose: the per-token scales ride their own pools
``(num_pages, ..., page_size)``, so the precision plan's ``kv_cache``
rule applies per page exactly as it applies per slab in dense layout.

**Prefix-cache page sharing** (``ServeConfig.kv_prefix_cache``, paged
layout): every *full* prompt page is registered in a prefix index under a
hash chain key — ``key_i = intern(key_{i-1}, tokens[i*ps:(i+1)*ps])`` —
so a page is only ever matched when its entire causal token prefix is
identical (keys are interned exact token tuples, never lossy hashes).
A same-prefix admission maps its leading block-table entries to the
matched pages and bumps their refcounts; only the unshared tail needs
pages (and, on the bit-exact float-GQA datapath, compute).  When a
request finishes, its refcount-0 registered pages are *retained* on an
evictable LRU instead of being wiped, so repeated-prompt workloads (the
same detector-geometry preamble across a physics batch) keep hitting
after the first tenant completes; allocation evicts the LRU tail only
under pool pressure.  A decode write aimed at a page with refcount > 1
triggers copy-on-write — allocate a fresh page, copy the pool rows,
swap the writer's table entry — and a write into a registered
refcount-1 page first drops the page from the index, so shared history
is immutable and every token stream stays bit-identical to the dense
layout.  Sharing, CoW bookkeeping, and preemption are host-side
block-table operations: the jitted program set does not grow.

**Host-memory victim tier** (``ServeConfig.kv_host_pages``): a fourth
page state behind the cached LRU.  A registered page evicted under pool
pressure spills its pool rows (every pool leaf — k/v, int8 scales, MLA
latents) into a host-side numpy ring of ``kv_host_pages`` pages instead
of discarding them, keeping its prefix-index chain key alive in a
host-tier index.  ``match_prefix`` walks past device coverage into that
index; admission then allocates fresh device pages for the host-covered
chunks and queues batched host->device row copies, applied by
:meth:`CacheManager.flush_swaps` at the executor's next dispatch
(exactly like CoW copies through :meth:`CacheManager.flush_copies`) —
so a warm prefix larger than the device pool admits as a normal prefix
hit with prefill-skip instead of recomputing.  All tier movement is
host bookkeeping plus eager device copies outside every jitted program:
the compiled program budget stays len(prefill_buckets) + 2.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ServeConfig

PyTree = Any

#: cache leaves with a sequence axis: name -> axis index from the right
SEQ_AXIS_FROM_RIGHT = {
    "k": 2, "v": 2, "latent": 2,  # (..., cache_len, feature)
    "k_scale": 1, "v_scale": 1, "latent_scale": 1,  # (..., cache_len)
}

#: leaves with a head axis ahead of the sequence axis in the dense layout
#: (batch, head, seq, ...) and in the view attention reads.  Their paged
#: pools store each token's heads contiguous: every pool is
#: (num_pages, page_size, token features...), and k/v flatten heads x dim
#: into one feature axis — the layout the programs scatter tokens into and
#: gather pages from, and the TPU's default layout for that shape, so no
#: program re-lays the pool out
_HEAD_MAJOR = ("k", "v", "k_scale", "v_scale")

#: reserved physical page id: write sink for masked/pad scatters, never read
TRASH_PAGE = 0

#: positions per slot that one round of a window write scatters
#: (:func:`_window_rounds`)
_WINDOW_ROWS = 32

LAYOUTS = ("dense", "paged")


# ---------------------------------------------------------------------------
# Per-layer attention cache specs (both layouts)
# ---------------------------------------------------------------------------


def attention_cache_spec(
    cfg: ModelConfig,
    batch: int,
    max_len: int,
    dtype=jnp.bfloat16,
    quantized: bool = False,
    layout: str = "dense",
    page_size: int | None = None,
    num_pages: int | None = None,
) -> dict:
    """Abstract per-layer attention cache (ShapeDtypeStruct); stacked by
    the caller.

    Dense GQA: (B, Hkv, L, D) k/v slabs.  Sliding window: rolling buffer
    of length ``window`` + slot positions.  MLA: packed latent
    (B, L, kv_lora + rope_dim).  quantized=True adds int8 codes +
    per-token f32 scales (the paper's fixed-point datapath applied to
    the KV cache, KIVI-style).

    Paged: k/v (or latent) pools keyed by physical page —
    (num_pages, page_size, Hkv * D) / (num_pages, page_size, width) —
    plus a per-slot ``page_table`` (batch, max_len // page_size) of
    physical page ids.  Scale pools mirror their data pools per page:
    (num_pages, page_size, Hkv) / (num_pages, page_size).
    """
    if layout not in LAYOUTS:
        raise ValueError(f"unknown kv layout {layout!r}; use one of {LAYOUTS}")
    if layout == "paged":
        return _paged_attention_cache_spec(
            cfg, batch, max_len, dtype, quantized, page_size, num_pages
        )
    if cfg.attn_kind == "none":
        return {}
    if cfg.attn_kind == "mla":
        m = cfg.mla
        width = m.kv_lora_rank + m.qk_rope_head_dim
        if quantized:
            return {
                "latent": jax.ShapeDtypeStruct(
                    (batch, max_len, width), jnp.int8
                ),
                "latent_scale": jax.ShapeDtypeStruct(
                    (batch, max_len), jnp.float32
                ),
            }
        return {
            "latent": jax.ShapeDtypeStruct((batch, max_len, width), dtype),
        }
    hd = cfg.resolved_head_dim
    length = max_len
    extra = {}
    if cfg.sliding_window is not None and cfg.sliding_window < max_len:
        length = cfg.sliding_window
        extra["slot_pos"] = jax.ShapeDtypeStruct((batch, length), jnp.int32)
    kv_dtype = jnp.int8 if quantized else dtype
    spec = {
        "k": jax.ShapeDtypeStruct((batch, cfg.n_kv_heads, length, hd), kv_dtype),
        "v": jax.ShapeDtypeStruct((batch, cfg.n_kv_heads, length, hd), kv_dtype),
        **extra,
    }
    if quantized:
        spec["k_scale"] = jax.ShapeDtypeStruct(
            (batch, cfg.n_kv_heads, length), jnp.float32
        )
        spec["v_scale"] = jax.ShapeDtypeStruct(
            (batch, cfg.n_kv_heads, length), jnp.float32
        )
    return spec


def _paged_attention_cache_spec(
    cfg, batch, max_len, dtype, quantized, page_size, num_pages
):
    if page_size is None or num_pages is None:
        raise ValueError("paged layout requires page_size and num_pages")
    if max_len % page_size != 0:
        raise ValueError(
            f"paged layout requires max_seq_len ({max_len}) to be a whole "
            f"number of pages (kv_page_size={page_size})"
        )
    if cfg.attn_kind not in ("gqa", "mla") or cfg.family in ("ssm", "hybrid"):
        raise ValueError(
            f"paged layout supports position-addressed GQA/MLA caches only "
            f"(got attn_kind={cfg.attn_kind!r}, family={cfg.family!r})"
        )
    if cfg.sliding_window is not None and cfg.sliding_window < max_len:
        raise ValueError(
            "paged layout does not support rolling sliding-window buffers"
        )
    pages_per_slot = max_len // page_size
    table = jax.ShapeDtypeStruct((batch, pages_per_slot), jnp.int32)
    if cfg.attn_kind == "mla":
        m = cfg.mla
        width = m.kv_lora_rank + m.qk_rope_head_dim
        spec = {
            "latent": jax.ShapeDtypeStruct(
                (num_pages, page_size, width),
                jnp.int8 if quantized else dtype,
            ),
        }
        if quantized:
            spec["latent_scale"] = jax.ShapeDtypeStruct(
                (num_pages, page_size), jnp.float32
            )
    else:
        hd = cfg.resolved_head_dim
        kv_dtype = jnp.int8 if quantized else dtype
        rows = (num_pages, page_size, cfg.n_kv_heads * hd)
        spec = {
            "k": jax.ShapeDtypeStruct(rows, kv_dtype),
            "v": jax.ShapeDtypeStruct(rows, kv_dtype),
        }
        if quantized:
            scales = (num_pages, page_size, cfg.n_kv_heads)
            spec["k_scale"] = jax.ShapeDtypeStruct(scales, jnp.float32)
            spec["v_scale"] = jax.ShapeDtypeStruct(scales, jnp.float32)
    spec["page_table"] = table
    return spec


def init_attention_cache(
    cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16, **kw
):
    spec = attention_cache_spec(cfg, batch, max_len, dtype, **kw)
    return {k: _zero_leaf(k, v) for k, v in spec.items()}


def _zero_leaf(name: str, s: jax.ShapeDtypeStruct):
    if name == "page_table":
        return jnp.full(s.shape, TRASH_PAGE, jnp.int32)
    if s.dtype == jnp.int32:
        return jnp.full(s.shape, -1, jnp.int32)  # invalid slot marker
    return jnp.zeros(s.shape, s.dtype)


# ---------------------------------------------------------------------------
# Stacked model-level caches (moved from models/lm.py)
# ---------------------------------------------------------------------------


def _per_layer_cache_spec(cfg, batch, max_len, dtype, quantized, **layout_kw):
    if cfg.family in ("ssm", "hybrid"):
        from repro.models import ssm  # runtime import: no module cycle

        return ssm.mamba_cache_spec(cfg, batch, jnp.float32)
    return attention_cache_spec(
        cfg, batch, max_len, dtype, quantized=quantized, **layout_kw
    )


def abstract_caches(
    cfg: ModelConfig,
    batch: int,
    max_len: int,
    dtype=jnp.bfloat16,
    quantized: bool = False,
    layout: str = "dense",
    page_size: int | None = None,
    num_pages: int | None = None,
) -> PyTree:
    """Stacked (leading layer axis) abstract caches for a whole model."""
    layout_kw = dict(layout=layout, page_size=page_size, num_pages=num_pages)
    per_layer = _per_layer_cache_spec(
        cfg, batch, max_len, dtype, quantized, **layout_kw
    )
    stacked = {
        k: jax.ShapeDtypeStruct((cfg.n_layers,) + v.shape, v.dtype)
        for k, v in per_layer.items()
    }
    caches: dict = {"layers": stacked}
    if cfg.family == "hybrid":
        # runtime imports: no module cycle (attention -> kv_cache)
        from repro.models import blocks, lm

        shared = blocks.shared_attn_cache_spec(cfg, batch, max_len, dtype)
        n_apps = lm.n_shared_apps(cfg)
        caches["shared"] = {
            k: jax.ShapeDtypeStruct((n_apps,) + v.shape, v.dtype)
            for k, v in shared.items()
        }
    return caches


def init_caches(
    cfg: ModelConfig,
    batch: int,
    max_len: int,
    dtype=jnp.bfloat16,
    quantized: bool = False,
    **layout_kw,
) -> PyTree:
    spec = abstract_caches(cfg, batch, max_len, dtype, quantized, **layout_kw)

    def _walk(node):
        if isinstance(node, dict):
            return {k: _walk_named(k, v) for k, v in node.items()}
        return node

    def _walk_named(name, node):
        if isinstance(node, dict):
            return {k: _walk_named(k, v) for k, v in node.items()}
        return _zero_leaf(name, node)

    return _walk(spec)


def cache_logical_axes(
    cfg: ModelConfig, quantized: bool = False, layout: str = "dense"
) -> PyTree:
    """Logical axes for cache sharding (distributed/sharding.py).

    Paged pools have no batch axis — they shard over heads (TP; a k/v
    row is head-major, so a split of it is a split of the heads) with
    the page axis replicated; the tiny page table shards over batch.
    """
    if layout == "paged":
        if cfg.attn_kind == "mla":
            per_layer = {"latent": ("layers", None, None, None)}
            if quantized:
                per_layer["latent_scale"] = ("layers", None, None)
        else:
            per_layer = {
                "k": ("layers", None, None, "kv_heads"),
                "v": ("layers", None, None, "kv_heads"),
            }
            if quantized:
                per_layer["k_scale"] = ("layers", None, None, "kv_heads")
                per_layer["v_scale"] = ("layers", None, None, "kv_heads")
        per_layer["page_table"] = ("layers", "batch", None)
        return {"layers": per_layer}
    kind = "mamba" if cfg.family in ("ssm", "hybrid") else cfg.attn_kind
    if kind == "mamba":
        per_layer = {
            "ssm_state": ("layers", "batch", "ssm_heads", None, None),
            "conv_state": ("layers", "batch", None, "inner"),
        }
    elif kind == "mla":
        per_layer = {"latent": ("layers", "batch", "cache_len", None)}
        if quantized:
            per_layer["latent_scale"] = ("layers", "batch", "cache_len")
    else:
        per_layer = {
            "k": ("layers", "batch", "kv_heads", "cache_len", None),
            "v": ("layers", "batch", "kv_heads", "cache_len", None),
        }
        if cfg.sliding_window is not None:
            per_layer["slot_pos"] = ("layers", "batch", None)
        if quantized:
            per_layer["k_scale"] = ("layers", "batch", "kv_heads", "cache_len")
            per_layer["v_scale"] = ("layers", "batch", "kv_heads", "cache_len")
    axes: dict = {"layers": per_layer}
    if cfg.family == "hybrid":
        axes["shared"] = {
            "k": ("layers", "batch", "kv_heads", "cache_len", None),
            "v": ("layers", "batch", "kv_heads", "cache_len", None),
        }
    return axes


# ---------------------------------------------------------------------------
# Traced helpers: cache read/write views (used by models/attention.py)
# ---------------------------------------------------------------------------

#: key of the layer index in a cache handed to one layer as the whole
#: stacked cache (see :func:`layer_cache`)
LAYER = "layer"


def is_paged(cache: dict | None) -> bool:
    """A per-layer cache dict is paged iff it carries a page table."""
    return cache is not None and "page_table" in cache


def rides_layer_carry(group: dict | None) -> bool:
    """Whether a stacked cache group is updated in place through the
    layer loop.

    Position-addressed caches (paged pools, dense slabs, a prefill's
    scratch slab) ride the layer scan's carry: each layer writes its
    tokens into, and reads its pages from, the stacked buffers at its
    own layer index, so no op materialises a whole layer's pool.
    State-sized caches (SSM state, rolling sliding-window buffers) are
    sliced per layer instead."""
    if not group:
        return False
    return "page_table" in group or (
        "slot_pos" not in group
        and any(name in SEQ_AXIS_FROM_RIGHT for name in group)
    )


def layer_cache(stacked: dict, layer: jax.Array) -> dict:
    """The cache one layer addresses in place: every stacked leaf
    (leading layer axis) plus the layer's index.  The helpers below take
    either form, so attention code never knows which one it holds."""
    return {**stacked, LAYER: layer}


def stacked_cache(cache: dict) -> dict:
    """The stacked leaves of a :func:`layer_cache`, its index dropped."""
    return {k: v for k, v in cache.items() if k != LAYER}


def _lead(cache: dict) -> tuple:
    """Index prefix that selects the cache's own layer: ``(layer,)`` for
    a :func:`layer_cache`, ``()`` for a per-layer cache."""
    return (cache[LAYER],) if LAYER in cache else ()


def _page_table(cache: dict) -> jax.Array:
    table = cache["page_table"]
    return table[cache[LAYER]] if LAYER in cache else table


def paged_decode_write(
    cache: dict, updates: dict[str, jax.Array], positions: jax.Array
) -> dict:
    """Scatter one token per slot into its physical page.

    ``updates``: leaf name -> per-slot values with the seq axis removed
    (k/v: (B, Hkv, D); scales: (B, Hkv); latent: (B, width);
    latent_scale: (B,)).  ``positions``: (B,) global write positions.
    Retired slots have all-trash page tables, so their (frozen) writes
    land in the reserved trash page and never alias live data.
    """
    table = _page_table(cache)  # (B, pages_per_slot)
    lead = _lead(cache)
    out = dict(cache)
    for name, val in updates.items():
        pool = cache[name]
        ps = pool.shape[len(lead) + 1]
        phys = jnp.take_along_axis(
            table, (positions // ps)[:, None], axis=1
        )[:, 0]  # (B,)
        off = positions % ps
        row = val.reshape(val.shape[:1] + pool.shape[len(lead) + 2:])
        out[name] = pool.at[lead + (phys, off)].set(row.astype(pool.dtype))
    return out


def dense_decode_write(
    cache: dict, updates: dict[str, jax.Array], slots: jax.Array
) -> dict:
    """Scatter one token per slot into a dense cache.

    Same ``updates`` shapes as :func:`paged_decode_write`; ``slots``:
    (B,) sequence indices to write (the position, or its rolling-buffer
    slot)."""
    lead = _lead(cache)
    out = dict(cache)
    b = slots.shape[0]
    for name, val in updates.items():
        buf = cache[name]
        if name in _HEAD_MAJOR:
            idx = (
                jnp.arange(b)[:, None],
                jnp.arange(val.shape[1])[None, :],
                slots[:, None],
            )
        else:
            idx = (jnp.arange(b), slots)
        out[name] = buf.at[lead + idx].set(val.astype(buf.dtype))
    return out


def dense_prefill_write(cache: dict, updates: dict[str, jax.Array]) -> dict:
    """Write each row's leading prompt positions into a dense cache (a
    prefill's fresh scratch slab).  ``updates``: leaf name -> per-slot
    prompt windows from position 0 (k/v: (B, Hkv, S, D); scales:
    (B, Hkv, S); latent: (B, S, width); latent_scale: (B, S))."""
    lead = _lead(cache)
    out = dict(cache)
    for name, val in updates.items():
        buf = cache[name]
        val = val.astype(buf.dtype)
        start = lead + (0,) * val.ndim
        if lead:
            val = val[None]
        out[name] = jax.lax.dynamic_update_slice(buf, val, start)
    return out


def _window_rounds(positions, rows, length):
    """Pad a window to whole rounds of ``_WINDOW_ROWS`` positions per slot
    (padding sits at ``length``, past every slot's rows, and is dropped)
    and count the rounds up to the last live position.  On the TPU each
    row a scatter is handed costs a copy into the cache whether it lands
    or is dropped, and a window is mostly padding, so the window writes
    scatter round by round and stop there."""
    w = positions.shape[1]
    size = min(_WINDOW_ROWS, w)
    pad = -w % size
    if pad:
        positions = jnp.pad(
            positions, ((0, 0), (0, pad)), constant_values=length
        )
        rows = {
            name: jnp.pad(r, ((0, 0), (0, pad)) + ((0, 0),) * (r.ndim - 2))
            for name, r in rows.items()
        }
    live_end = jnp.max(
        jnp.where(positions < length, jnp.arange(w + pad) + 1, 0)
    )
    return positions, rows, size, (live_end + size - 1) // size


def _scatter_rounds(cache, rows, positions, length, index):
    """Scatter (B, W, ...) window ``rows`` into ``cache`` round by round
    (:func:`_window_rounds`); ``index(name, p)`` maps one round's (B, n)
    positions to the leaf's scatter index after the layer prefix."""
    lead = _lead(cache)
    positions, rows, size, rounds = _window_rounds(positions, rows, length)

    def write_round(i, pools):
        at = i * size
        p = jax.lax.dynamic_slice_in_dim(positions, at, size, axis=1)
        return {
            name: pool.at[lead + index(name, p)].set(
                jax.lax.dynamic_slice_in_dim(rows[name], at, size, axis=1)
                .astype(pool.dtype),
                mode="drop",
            )
            for name, pool in pools.items()
        }

    pools = {name: cache[name] for name in rows}
    return {**cache, **jax.lax.fori_loop(0, rounds, write_round, pools)}


def dense_window_write(
    cache: dict, updates: dict[str, jax.Array], positions: jax.Array
) -> dict:
    """Scatter a token *window* per slot into a dense per-layer cache.

    The cache-extending prefill program's write primitive: where
    :func:`paged_decode_write` lands one token per slot,
    this lands a contiguous window of ``W`` tokens at arbitrary
    per-row offsets.  ``updates``: leaf name -> per-slot windows
    (k/v: (B, Hkv, W, D); scales: (B, Hkv, W); latent: (B, W, width);
    latent_scale: (B, W)).  ``positions``: (B, W) global write
    positions; masked entries carry an out-of-range sentinel
    (>= cache length) and are dropped by the scatter.
    """
    b = positions.shape[0]
    rows = {
        name: jnp.moveaxis(val, 2, 1) if name in _HEAD_MAJOR else val
        for name, val in updates.items()
    }  # (B, W, ...)
    first = next(iter(updates))
    length = cache[first].shape[-SEQ_AXIS_FROM_RIGHT[first]]

    def index(name, p):
        if name in _HEAD_MAJOR:  # rows (B, n, H[, D]) into (B, H, S[, D])
            heads = jnp.arange(rows[name].shape[2])
            return (jnp.arange(b)[:, None, None], heads[None, None, :],
                    p[:, :, None])
        return (jnp.arange(b)[:, None], p)

    return _scatter_rounds(cache, rows, positions, length, index)


def paged_window_write(
    cache: dict, updates: dict[str, jax.Array], positions: jax.Array
) -> dict:
    """Scatter a token window per slot into physical pages.

    Same update shapes and (B, W) ``positions`` contract as
    :func:`dense_window_write`.  Each position routes through the page
    table independently, so a window may straddle page boundaries.
    Sentinel positions index past the table and are dropped, never
    written, so masked entries never alias live data.
    """
    table = _page_table(cache)  # (B, pages_per_slot)
    lead = _lead(cache)
    rows = {}
    for name, val in updates.items():
        if name in _HEAD_MAJOR:
            val = jnp.moveaxis(val, 2, 1)  # (B, W, H[, D])
        feat = cache[name].shape[len(lead) + 2:]
        rows[name] = val.reshape(val.shape[:2] + feat)
    # every pool is (pages, page_size, ...) after the layer prefix
    past, ps = cache[next(iter(updates))].shape[len(lead):len(lead) + 2]

    def index(name, p):  # a position past the table goes past the pool
        phys = jnp.take_along_axis(
            table, p // ps, axis=1, mode="fill", fill_value=past
        )
        return (phys, p % ps)

    return _scatter_rounds(
        cache, rows, positions, table.shape[1] * ps, index
    )


def window_write(
    cache: dict, updates: dict[str, jax.Array], positions: jax.Array
) -> dict:
    """:func:`paged_window_write` or :func:`dense_window_write`, by the
    cache's layout."""
    write = paged_window_write if is_paged(cache) else dense_window_write
    return write(cache, updates, positions)


def paged_decode_view(
    cache: dict, kv_heads: int | None = None
) -> dict[str, jax.Array]:
    """Gather each slot's pages into a contiguous logical view.

    Returns dense-shaped arrays — k/v: (B, Hkv, L, D); scales:
    (B, Hkv, L); latent: (B, L, width); latent_scale: (B, L) — where
    ``L = pages_per_slot * page_size == max_seq_len``, so downstream
    attention math is bit-identical to the dense layout (unallocated
    entries read the trash page and are masked by position validity,
    exactly like dense positions beyond the write head).  ``kv_heads``
    splits a k/v row back into its heads (GQA pools).  A
    :func:`layer_cache` gathers straight from the stacked pool at
    ``[layer, table]``.
    """
    table = _page_table(cache)  # (B, pages_per_slot)
    lead = _lead(cache)
    out = {}
    for name, pool in cache.items():
        if name in ("page_table", LAYER):
            continue
        g = pool[lead + (table,)]  # (B, n_pages, ps, ...)
        g = g.reshape((g.shape[0], g.shape[1] * g.shape[2]) + g.shape[3:])
        if name in _HEAD_MAJOR:
            dim = (-1,) if SEQ_AXIS_FROM_RIGHT[name] == 2 else ()
            g = g.reshape(g.shape[:2] + (kv_heads,) + dim)
            g = jnp.moveaxis(g, 2, 1)  # (B, Hkv, L[, D])
        out[name] = g
    return out


def decode_view(
    cache: dict, kv_heads: int | None = None
) -> dict[str, jax.Array]:
    """The contiguous per-slot view one layer's attention reads: the
    page gather of :func:`paged_decode_view`, or the dense slab itself
    (its own layer's, for a :func:`layer_cache`)."""
    if is_paged(cache):
        return paged_decode_view(cache, kv_heads)
    if LAYER not in cache:
        return cache
    layer = cache[LAYER]
    return {k: v[layer] for k, v in cache.items() if k != LAYER}


def pool_leaf(caches: PyTree) -> jax.Array:
    """One storage leaf of the stacked model caches (never the page
    table): a program that consumed its caches argument deleted it."""
    return next(
        leaf for name, leaf in caches["layers"].items()
        if name != "page_table"
    )


def decode_read_len(caches: PyTree) -> int:
    """KV positions per slot that one decode step's attention reads from
    ``caches`` (the stacked model caches a decode program is called
    with): the page-table view (pages per slot x page size) on the paged
    layout, the slab on the dense one (the rolling buffer for a sliding
    window), 0 where no layer keeps attention state (SSM)."""
    for group in (caches.get("layers"), caches.get("shared")):
        for name in ("k", "latent"):
            if group and name in group:
                n = group[name].shape[-2]  # page size, or the slab length
                if "page_table" in group:
                    n *= group["page_table"].shape[-1]
                return int(n)
    return 0


# ---------------------------------------------------------------------------
# Traced helpers: prefill masking + layout-specific slot insertion
# ---------------------------------------------------------------------------


def mask_cache_tail(filled: PyTree, lengths: jax.Array) -> PyTree:
    """Zero cache entries at positions >= the per-row prompt length.

    ``filled``: stacked dense caches with batch axis 1 on every leaf.
    ``lengths``: (N,) true prompt lengths (traced, so every same-bucket
    batch reuses one compiled program).  Leaves without a sequence axis
    (SSM state, slot_pos) pass through; those families use exact-length
    prefill anyway, where the mask is all-true.
    """

    def _mask_group(group):
        out = {}
        for name, leaf in group.items():
            axis_r = SEQ_AXIS_FROM_RIGHT.get(name)
            if axis_r is None:
                out[name] = leaf
                continue
            axis = leaf.ndim - axis_r
            seq = jnp.arange(leaf.shape[axis])
            seq_b = seq.reshape(
                (1,) * axis + (-1,) + (1,) * (leaf.ndim - axis - 1)
            )
            len_b = lengths.reshape((1, -1) + (1,) * (leaf.ndim - 2))
            out[name] = jnp.where(
                seq_b < len_b, leaf, jnp.zeros((), leaf.dtype)
            )
        return out

    return {k: _mask_group(v) for k, v in filled.items()}


def insert_prefill_dense(big: PyTree, filled: PyTree, slots: jax.Array):
    """Scatter freshly prefilled rows into their slots (batch axis 1 on
    every stacked leaf).  Rows whose slot index is out of range (the
    engine's padding sentinel) are dropped."""

    def ins(b, f):
        return b.at[:, slots].set(f.astype(b.dtype), mode="drop")

    return jax.tree.map(ins, big, filled)


def insert_prefill_paged(
    big: PyTree, filled: PyTree, slots: jax.Array, page_size: int,
    shared_pages: jax.Array | None = None,
):
    """Scatter dense prefilled rows into each slot's physical pages.

    ``filled`` is the dense scratch cache the model wrote (tail-masked);
    it may be shorter than the full logical range — the engine sizes it
    to the prefill bucket rounded up to whole pages.  Its page view is
    scattered through the leading columns of the slots' page-table rows;
    later logical pages stay untouched (any stale tenant data there is
    masked by position validity until decode overwrites each position as
    it becomes valid).  Unallocated table entries — the pad tail beyond
    a prompt's allocated pages, and entire rows for padding slots —
    point at the trash page, so those writes are inert.

    ``shared_pages``: optional (N,) per-row count of leading table
    entries that alias prefix-cache pages owned by earlier requests.
    Those columns are redirected to the trash page for this scatter, so
    the (recomputed, bit-identical) prefix values never touch shared
    storage — shared history stays immutable without copy-on-write.
    """
    layers = dict(big["layers"])
    table = layers["page_table"][0]  # identical across layers: (B, n_pages)
    row_tables = jnp.take(
        table, slots, axis=0, mode="fill", fill_value=TRASH_PAGE
    )  # (N, pages_per_slot)
    if shared_pages is not None:
        col = jnp.arange(row_tables.shape[1], dtype=jnp.int32)
        row_tables = jnp.where(
            col[None, :] < shared_pages[:, None], TRASH_PAGE, row_tables
        )
    for name, small in filled["layers"].items():
        pool = layers[name]
        axis = small.ndim - SEQ_AXIS_FROM_RIGHT[name]
        n_pages = small.shape[axis] // page_size
        paged_shape = (
            small.shape[:axis] + (n_pages, page_size) + small.shape[axis + 1:]
        )
        pages = small.reshape(paged_shape)  # (L, N, [H,] n_pages, ps, ...)
        if name in _HEAD_MAJOR:
            pages = jnp.moveaxis(pages, 2, axis + 1)  # heads after the offset
        # (L, N, n_pages, ps) + the pool's token features
        pages = pages.reshape(pages.shape[:4] + pool.shape[3:])
        # pool (L, P, ...), indices (N, n_pages) on axis 1
        layers[name] = pool.at[:, row_tables[:, :n_pages]].set(
            pages.astype(pool.dtype)
        )
    return {"layers": layers}


# ---------------------------------------------------------------------------
# Host-side manager
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CacheStats:
    layout: str
    kv_bytes: int
    page_size: int
    pages_in_use: int
    pages_capacity: int
    page_allocs_total: int
    pages_in_use_peak: int
    pages_cached: int = 0
    prefix_queries: int = 0
    prefix_hits: int = 0
    prefix_pages_hit: int = 0
    cow_copies: int = 0
    page_evictions: int = 0
    #: pages shared by mapping a resident parent's live pages onto an
    #: n-best sibling (CacheManager.fork) — generated-into pages
    #: included, unlike prefix-index hits which only ever share fully
    #: prompt-written pages
    gen_pages_shared: int = 0
    #: victim-tier movement: pages spilled to the host ring on eviction
    #: (swap_outs), spilled pages fetched back into device pages on a
    #: later prefix hit (swap_ins), and spilled pages dropped when the
    #: host ring itself overflowed (host_evictions)
    swap_outs: int = 0
    swap_ins: int = 0
    host_evictions: int = 0
    host_pages_used: int = 0
    host_pages_capacity: int = 0
    #: host wall seconds spent in flush_swaps (device<->host row copies)
    swap_latency_s: float = 0.0

    @property
    def page_utilization(self) -> float:
        # a dense manager built with max_batch=0 (spec-only probes) or a
        # hand-rolled stats row may carry zero capacity
        if self.pages_capacity <= 0:
            return 0.0
        return self.pages_in_use / self.pages_capacity

    @property
    def prefix_hit_rate(self) -> float:
        if self.prefix_queries <= 0:
            return 0.0
        return self.prefix_hits / self.prefix_queries

    def as_dict(self) -> dict:
        return {
            "kv_layout": self.layout,
            "kv_bytes": self.kv_bytes,
            "kv_page_size": self.page_size,
            "pages_in_use": self.pages_in_use,
            "pages_capacity": self.pages_capacity,
            "page_utilization": self.page_utilization,
            "page_allocs_total": self.page_allocs_total,
            "pages_in_use_peak": self.pages_in_use_peak,
            "pages_cached": self.pages_cached,
            "prefix_queries": self.prefix_queries,
            "prefix_hits": self.prefix_hits,
            "prefix_hit_rate": self.prefix_hit_rate,
            "prefix_pages_hit": self.prefix_pages_hit,
            "cow_copies": self.cow_copies,
            "page_evictions": self.page_evictions,
            "gen_pages_shared": self.gen_pages_shared,
            "swap_outs": self.swap_outs,
            "swap_ins": self.swap_ins,
            "host_evictions": self.host_evictions,
            "host_pages_used": self.host_pages_used,
            "host_pages_capacity": self.host_pages_capacity,
            "swap_latency_s": self.swap_latency_s,
        }


@dataclasses.dataclass(frozen=True)
class PrefixMatch:
    """Longest prefix-index match for a prompt.  ``keys[i]`` is the
    interned chain key of token chunk ``i`` (all full pages); the
    leading ``len(pages)`` chunks are device-resident (``pages[i]``
    holds chunk ``i``'s KV), the remaining ``host_hits`` chunks live in
    the host victim tier and swap back in at admission.  Without a
    victim tier ``len(keys) == len(pages)``.  ``tokens`` ==
    ``len(keys) * page_size`` — total coverage across both tiers."""

    pages: tuple[int, ...] = ()
    keys: tuple[int, ...] = ()
    tokens: int = 0

    @property
    def host_hits(self) -> int:
        """Matched chunks resident only in the host victim tier."""
        return len(self.keys) - len(self.pages)

    def __bool__(self) -> bool:
        return bool(self.keys)


class CacheManager:
    """Owns the KV-cache storage layout for one serving engine.

    Host-side responsibilities: building the device cache pytree,
    page allocation / reclamation / refcounting per slot (paged layout),
    the prefix-cache index (hash-chained full prompt pages, shared
    copy-on-write), and keeping the device page table in sync.  Traced
    responsibility: inserting a prefilled dense slab into the big caches
    inside the engine's jitted prefill program (:meth:`insert_prefill` —
    static layout config only, so it adds no jit programs).

    Dense layout is modeled as one page of ``max_seq_len`` tokens per
    slot, statically bound to the slot — which makes the occupancy
    telemetry uniform across layouts.  Prefix caching degenerates to a
    no-op for dense (slot-bound slabs cannot be shared).

    Paged page lifecycle: ``free`` (no meaningful content) -> ``live``
    (refcount >= 1, owned by one or more slot tables) -> either back to
    ``free`` (unregistered content) or ``cached`` (refcount 0 but still
    registered in the prefix index, evictable LRU) when its last owner
    finishes.  The reserved trash page 0 never enters any of the three
    sets.  With a victim tier (``ServeConfig.kv_host_pages``) eviction
    off the cached LRU adds a fourth, host-side state: ``spilled`` —
    the page's rows live in the host ring under its chain key, and a
    later prefix hit swaps them back into a fresh device page
    (:meth:`flush_swaps`); the tier-LRU eviction of a spilled chain is
    the only point where warm prefix state is truly discarded.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        serve_cfg: ServeConfig,
        quantized: bool = False,
        dtype=jnp.float32,
    ):
        self.cfg = cfg
        self.serve_cfg = serve_cfg
        self.quantized = quantized
        self.dtype = dtype
        sc = serve_cfg
        rolling = (
            cfg.sliding_window is not None
            and cfg.sliding_window < sc.max_seq_len
        )
        #: position-addressed caches can be right-padded (bucketed
        #: prefill) and paged; SSM/hybrid state and rolling buffers can't
        self.position_addressed = (
            cfg.attn_kind in ("gqa", "mla")
            and cfg.family not in ("ssm", "hybrid")
            and not rolling
        )
        requested = sc.kv_layout
        if requested not in LAYOUTS:
            raise ValueError(
                f"unknown kv_layout {requested!r}; use one of {LAYOUTS}"
            )
        self.layout = (
            "paged"
            if requested == "paged" and self.position_addressed
            else "dense"
        )
        if self.layout == "paged":
            ps = sc.kv_page_size
            if ps < 1 or sc.max_seq_len % ps != 0:
                raise ValueError(
                    f"kv_page_size={ps} must divide max_seq_len="
                    f"{sc.max_seq_len} (fixed-stride pages)"
                )
            self.page_size = ps
            self.pages_per_slot = sc.max_seq_len // ps
            auto = sc.max_batch * self.pages_per_slot + 1  # +1 trash page
            self.num_pages = auto if sc.kv_pages is None else sc.kv_pages
            if self.num_pages < 2:
                raise ValueError("kv_pages must be >= 2 (one is the trash page)")
            # page 0 is the reserved trash page; pop() allocates ascending
            self._free = list(range(self.num_pages - 1, 0, -1))
        else:
            # dense: one slot-bound "page" of max_seq_len tokens
            self.page_size = sc.max_seq_len
            self.pages_per_slot = 1
            self.num_pages = sc.max_batch
            self._free = []
        #: prefix-cache sharing is a paged-layout feature; dense slabs are
        #: slot-bound and the knob is silently inert there
        self.prefix_cache = bool(sc.kv_prefix_cache and self.layout == "paged")
        self._slot_pages: list[list[int]] = [[] for _ in range(sc.max_batch)]
        # worst-case pages promised to each resident request at admission;
        # allocation stays lazy, but admission never over-promises the pool
        self._slot_reserved: list[int] = [0] * sc.max_batch
        #: per-slot interned chain keys for pages [0, len(keys)) — the
        #: registration watermark, so register_filled only chunks/interns
        #: pages completed since its previous call (truncated when a
        #: write mutates a chained page, i.e. CoW / deregister-on-write)
        self._slot_keys: list[list[int]] = [[] for _ in range(sc.max_batch)]
        self._table = np.zeros(
            (sc.max_batch, self.pages_per_slot), np.int32
        )
        self._table_dirty = True
        self._allocs_total = 0
        self._peak_in_use = 0
        # --- refcounts + prefix index (paged sharing) ---
        self._page_ref = np.zeros(self.num_pages, np.int32)
        #: retained refcount-0 registered pages, insertion order == LRU
        self._cached: dict[int, None] = {}
        #: interned hash-chain keys: (parent_key, token chunk) -> key id.
        #: Keys are exact token tuples (no lossy hashing), so two distinct
        #: prefixes can never collide into the same page.  Ids come from a
        #: monotonic counter (never reused), and the table is mark-swept
        #: once it doubles past the reachable set (_maybe_gc_intern) so a
        #: long-running server does not leak an entry per page ever served.
        self._key_intern: dict[tuple[int, tuple[int, ...]], int] = {}
        self._next_key_id = 1
        self._intern_gc_floor = 1024
        self._intern_gc_at = self._intern_gc_floor
        self._prefix_index: dict[int, int] = {}  # key id -> physical page
        self._page_key: dict[int, int] = {}  # physical page -> key id
        #: device page copies scheduled by copy-on-write, flushed by the
        #: engine (flush_copies) before the next decode dispatch
        self._pending_copies: list[tuple[int, int]] = []
        self._cow_copies = 0
        self._evictions = 0
        self._gen_pages_shared = 0
        self._prefix_queries = 0
        self._prefix_hits = 0
        self._prefix_pages_hit = 0
        # --- host-memory victim tier (kv_host_pages) ---
        #: tier on: registered pages evicted off the device LRU spill
        #: their pool rows into host numpy rings instead of vanishing
        self.victim_tier = bool(
            self.prefix_cache
            and getattr(sc, "kv_victim_tier", True)
            and getattr(sc, "kv_host_pages", 0) > 0
        )
        self.host_pages = sc.kv_host_pages if self.victim_tier else 0
        #: per-pool host rings, (n_layers, host_pages, per-page dims...)
        #: mirroring every device pool leaf (k/v, scales, latents — never
        #: the page table)
        self._host_pool: dict[str, np.ndarray] = {}
        if self.victim_tier:
            for name, leaf in self._abstract()["layers"].items():
                if name == "page_table":
                    continue
                self._host_pool[name] = np.zeros(
                    (leaf.shape[0], self.host_pages) + leaf.shape[2:],
                    leaf.dtype,
                )
        self._host_free: list[int] = list(range(self.host_pages - 1, -1, -1))
        #: chain key -> host ring slot, insertion order == tier LRU
        self._host_index: dict[int, int] = {}
        self._host_key: dict[int, int] = {}  # host slot -> chain key
        #: host-tier keys the current admit() must not evict while it
        #: allocates their swap-in device pages (a fetch's own device
        #: allocation can evict a cached page, whose spill could
        #: otherwise recycle a host slot the same admission still needs)
        self._host_pins: set[int] = set()
        #: queued device->host row copies (evictions of warm pages) and
        #: host->device copies (prefix hits on spilled chains), both
        #: applied by flush_swaps at the executor's next dispatch
        self._pending_spills: list[tuple[int, int]] = []  # (page, host slot)
        self._pending_swap_ins: list[tuple[int, int]] = []  # (host slot, page)
        #: device page -> (host slot, chain key) for unflushed swap-ins,
        #: so eviction/free of the target page can cancel the copy and
        #: restore the key to the host tier (the rows never left it)
        self._swap_in_by_page: dict[int, tuple[int, int]] = {}
        self._swap_ins = 0
        self._swap_outs = 0
        self._host_evictions = 0
        self._swap_latency_s = 0.0
        self.kv_bytes = sum(
            int(np.prod(leaf.shape)) * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(self._abstract())
        )
        #: NamedSharding for the page_table leaf (set by the executor
        #: when shard_decode is on).  write_table rebuilds the table
        #: from host numpy each sync; without re-placing it onto the
        #: mesh the rebuilt leaf would arrive with default (single
        #: device) sharding and re-key the decode jit cache — a second
        #: compiled decode program, blowing the budget.
        self.table_sharding = None

    # ----------------------------------------------------------- layout --
    def _layout_kw(self) -> dict:
        if self.layout == "paged":
            return dict(
                layout="paged",
                page_size=self.page_size,
                num_pages=self.num_pages,
            )
        return dict(layout="dense")

    def _abstract(self) -> PyTree:
        return abstract_caches(
            self.cfg, self.serve_cfg.max_batch, self.serve_cfg.max_seq_len,
            dtype=self.dtype, quantized=self.quantized, **self._layout_kw(),
        )

    def init_device_caches(self, shardings: PyTree | None = None) -> PyTree:
        """The zeroed device caches; with ``shardings`` (the tree
        :meth:`device_shardings` gives) each leaf is made in place under
        its sharding by one jitted fill, so no device holds more than its
        share."""

        def make():
            return init_caches(
                self.cfg, self.serve_cfg.max_batch,
                self.serve_cfg.max_seq_len, dtype=self.dtype,
                quantized=self.quantized, **self._layout_kw(),
            )

        if shardings is None:
            return make()
        return jax.jit(make, out_shardings=shardings)()

    def device_shardings(self, rules) -> PyTree:
        """NamedSharding tree matching :meth:`init_device_caches` for the
        given :class:`~repro.distributed.sharding.ShardingRules` — the
        executor builds the live caches under it when
        ``ServeConfig.shard_decode`` is on, and stores the page_table
        leaf in :attr:`table_sharding` so :meth:`write_table` rebuilds
        land on the same placement."""
        from repro.distributed.sharding import cache_shardings

        return cache_shardings(
            rules, self.cfg, self.serve_cfg.max_batch,
            self.serve_cfg.max_seq_len, quantized=self.quantized,
            **self._layout_kw(),
        )

    # ------------------------------------------------------- allocation --
    def pages_for(self, length: int) -> int:
        """Pages needed to hold ``length`` tokens (at least one)."""
        return max(1, -(-length // self.page_size))

    @property
    def pages_reserved_unallocated(self) -> int:
        """Reserved-but-not-yet-allocated pages (promised decode headroom)."""
        return sum(
            max(r - len(p), 0)
            for r, p in zip(self._slot_reserved, self._slot_pages)
        )

    def can_reserve(self, n_pages: int) -> bool:
        """Whether the pool can promise ``n_pages`` to a new request without
        eating another resident request's unallocated reservation.  Cached
        (refcount-0 retained) pages count as available: allocation evicts
        them LRU under pressure."""
        if self.layout != "paged":
            return True  # dense slabs are slot-bound; engine gates on slots
        avail = len(self._free) + len(self._cached)
        return avail - self.pages_reserved_unallocated >= n_pages

    def _take_page(self) -> int | None:
        """Pop a free page, evicting the LRU cached page when the free list
        is empty.  With a victim tier, the evicted page's rows spill to
        the host ring (its chain key stays fetchable) instead of being
        discarded.  Returns None when the pool is truly exhausted."""
        if self._free:
            return self._free.pop()
        if self._cached:
            page = next(iter(self._cached))
            del self._cached[page]
            self._spill(page)
            self._evictions += 1
            return page
        return None

    def _spill(self, page: int) -> None:
        """Deregister an evicted page; with a victim tier, move its chain
        key into the host index and queue the device->host row copy for
        :meth:`flush_swaps` (the copy must land before the page's new
        owner writes it — guaranteed because every dispatch flushes
        swaps at host_prep, ahead of its device program).  Degenerates
        to plain deregistration when the tier is off or the host ring
        has no evictable slot left."""
        key = self._page_key.pop(page, None)
        if key is not None and self._prefix_index.get(key) == page:
            del self._prefix_index[key]
        if not self.victim_tier or key is None:
            return
        if page in self._swap_in_by_page:
            # the page's content is itself an unflushed swap-in: the
            # chain rows never left the host ring, so cancel the copy
            # and re-register the key on its still-valid host slot
            self._cancel_swap_in(page)
            return
        host = self._host_take()
        if host is None:
            return  # ring exhausted (all pinned/absent): classic discard
        self._host_index[key] = host
        self._host_key[host] = key
        self._pending_spills.append((page, host))
        self._swap_outs += 1

    def _host_take(self) -> int | None:
        """Pop a free host ring slot, evicting the tier-LRU chain (for
        real — its rows are gone) when the ring is full.  Keys pinned by
        an in-progress admission are never victims."""
        if self._host_free:
            return self._host_free.pop()
        victim = next(
            (k for k in self._host_index if k not in self._host_pins), None
        )
        if victim is None:
            return None
        host = self._host_index.pop(victim)
        del self._host_key[host]
        # a spill aimed at the recycled slot that never flushed is
        # superseded by the new tenant's rows — drop it
        self._pending_spills = [
            (p, h) for p, h in self._pending_spills if h != host
        ]
        self._host_evictions += 1
        return host

    def _cancel_swap_in(self, page: int) -> None:
        """Cancel the unflushed host->device copy aimed at ``page``
        (the page is being evicted or freed before any dispatch flushed
        it) and restore its chain key onto the host slot, whose rows are
        still intact."""
        host, key = self._swap_in_by_page.pop(page)
        self._pending_swap_ins = [
            (h, p) for h, p in self._pending_swap_ins if p != page
        ]
        if key not in self._host_index and key not in self._prefix_index:
            self._host_index[key] = host
            self._host_key[host] = key
        elif host not in self._host_key:
            self._host_free.append(host)

    def _fetch_host(self, key: int) -> int:
        """Swap one spilled chain page back: allocate a fresh device
        page, queue the host->device row copy for :meth:`flush_swaps`,
        and re-register the key on the device page (the host slot frees
        once the copy lands).  Callers have already counted this
        allocation in :meth:`admission_need`."""
        host = self._host_index.pop(key)
        del self._host_key[host]
        page = self._take_page()
        if page is None:
            # unreachable under the admission discipline (the fetch was
            # charged to admission_need); restore the host entry and
            # fail loudly rather than corrupt the chain
            self._host_index[key] = host
            self._host_key[host] = key
            raise RuntimeError(
                "KV page pool exhausted during victim-tier swap-in; "
                "check can_reserve(admission_need(...)) before admit()"
            )
        self._pending_swap_ins.append((host, page))
        self._swap_in_by_page[page] = (host, key)
        self._prefix_index[key] = page
        self._page_key[page] = key
        self._swap_ins += 1
        self._allocs_total += 1
        return page

    def _deregister(self, page: int) -> None:
        key = self._page_key.pop(page, None)
        if key is None:
            return
        if self._prefix_index.get(key) == page:
            del self._prefix_index[key]
        entry = self._swap_in_by_page.get(page)
        if (
            entry is not None
            and entry[1] == key
            and key not in self._host_index
        ):
            # deregistered by a mid-tenancy write before its swap-in
            # flushed: the device copy is about to diverge, but the host
            # ring still holds the chain's original rows — keep the key
            # fetchable there (the pending copy still runs: positions
            # below the write still need the swapped content)
            host = entry[0]
            self._host_index[key] = host
            self._host_key[host] = key

    def _intern_key(self, parent: int, chunk: tuple[int, ...]) -> int:
        key = self._key_intern.get((parent, chunk))
        if key is None:
            key = self._next_key_id
            self._next_key_id += 1
            self._key_intern[(parent, chunk)] = key
            self._maybe_gc_intern()
        return key

    def _maybe_gc_intern(self) -> None:
        """Mark-sweep the chain-key intern table once it doubles past its
        last post-sweep size: keep only keys reachable (via parent links)
        from a registered page or a resident slot's chain watermark.
        Without this, every full page of every request ever served leaves
        an entry behind — an unbounded host-memory leak on long-running
        engines.  Dropped prefixes simply re-intern under fresh ids (the
        monotonic counter guarantees no id is ever reused)."""
        if len(self._key_intern) <= self._intern_gc_at:
            return
        parent_of = {
            kid: parent for (parent, _), kid in self._key_intern.items()
        }
        live: set[int] = set()
        roots = list(self._prefix_index)
        for keys in self._slot_keys:
            roots.extend(keys)
        for key in roots:
            while key and key not in live:
                live.add(key)
                key = parent_of.get(key, 0)
        self._key_intern = {
            pk: kid for pk, kid in self._key_intern.items() if kid in live
        }
        self._intern_gc_at = max(
            self._intern_gc_floor, 2 * len(self._key_intern)
        )

    # ----------------------------------------------------- prefix cache --
    def match_prefix(self, tokens: list[int]) -> PrefixMatch:
        """Longest run of leading *full* prompt pages already present in
        the prefix index — device-resident pages first, then (victim
        tier) chain keys whose rows live in the host ring and will swap
        back in at admission.  The device run must stay leading (shared
        pages sit at identical table columns in every owner), so the
        walk ends at the first chunk found in neither tier, or at a
        device-resident chunk that follows a host hit.  Pure lookup —
        hit/query telemetry is counted at :meth:`admit` so admission
        retries don't inflate the rate."""
        if not self.prefix_cache:
            return PrefixMatch()
        parent = 0
        pages: list[int] = []
        keys: list[int] = []
        for i in range(len(tokens) // self.page_size):
            chunk = tuple(tokens[i * self.page_size:(i + 1) * self.page_size])
            key = self._key_intern.get((parent, chunk))
            if key is None:
                break
            page = self._prefix_index.get(key)
            if page is not None and len(keys) == len(pages):
                pages.append(page)
            elif key not in self._host_index:
                break
            keys.append(key)
            parent = key
        return PrefixMatch(
            tuple(pages), tuple(keys), len(keys) * self.page_size
        )

    def _tail_need(
        self, match: PrefixMatch | None, reserve_len: int, write_from: int
    ) -> int:
        """Pages this admission will still have to allocate beyond its
        shared coverage (device-matched plus swapped-in chunks): the
        uncovered tail, plus one copy-on-write headroom page when the
        first decode write lands inside a covered page (a full-coverage
        prefix hit)."""
        total = self.pages_for(min(reserve_len, self.serve_cfg.max_seq_len))
        shared = len(match.keys) if match else 0
        headroom = 1 if match and write_from < match.tokens else 0
        return max(total - shared, 0) + headroom

    def _revived(self, match: PrefixMatch | None) -> int:
        """Matched pages currently on the cached LRU (refcount 0): mapping
        them revives them, removing them from the evictable pool, so the
        admission check must count them against availability even though
        they are not fresh allocations."""
        if not match:
            return 0
        return sum(1 for p in match.pages if self._page_ref[p] == 0)

    def admission_need(
        self, match: PrefixMatch | None, reserve_len: int, write_from: int
    ) -> int:
        """Pages the pool must have available (free + evictable-cached,
        net of other residents' unallocated reservations) to admit this
        request: its uncovered tail's worst case, plus any cached
        matched pages its admission revives, plus one fresh device page
        per host-tier hit (each swapped-in chunk lands in a new device
        page)."""
        if self.layout != "paged":
            return 0
        return (
            self._tail_need(match, reserve_len, write_from)
            + self._revived(match)
            + (match.host_hits if match else 0)
        )

    def admit(
        self,
        slot: int,
        tokens: list[int],
        reserve_len: int,
        match: PrefixMatch | None = None,
        lazy_tail: bool = False,
        write_from: int | None = None,
        fill_len: int | None = None,
    ) -> int:
        """Admit a request: map any prefix-cache hit onto the slot's
        leading table entries (refcount++, reviving retained pages; a
        host-tier continuation allocates fresh device pages and queues
        their swap-in row copies for :meth:`flush_swaps`), reserve
        worst-case pages for the uncovered remainder
        (``reserve_len`` = prompt + generation budget, capped at
        max_seq_len), then allocate — and register in the prefix index —
        the prompt's own pages.  ``lazy_tail=True`` skips the prompt-tail
        allocation (the engine's prefill-skip path fills the tail through
        decode writes, so :meth:`ensure` allocates it lazily like any
        decode growth); ``fill_len`` (chunked prefill) allocates and
        registers only the leading ``fill_len`` positions now — the
        prefill dispatch writes exactly those — leaving the rest lazy.
        Returns the number of covered leading pages (device-shared plus
        swapped-in).

        Reservation is a counter, not an allocation — but admission-time
        reservation guarantees decode growth (including at most one
        copy-on-write allocation) can never exhaust the pool mid-run."""
        if write_from is None:
            write_from = len(tokens)
        if self.layout != "paged":
            self.alloc(slot, len(tokens))
            return 0
        if self.prefix_cache:
            self._prefix_queries += 1
        shared = list(match.pages) if match else []
        swapped = match.host_hits if match else 0
        need = self.admission_need(match, reserve_len, write_from)
        if not self.can_reserve(need):
            raise RuntimeError(
                f"cannot reserve {need} KV pages for admission; check "
                "can_reserve() before calling admit()"
            )
        tail_need = self._tail_need(match, reserve_len, write_from)
        if shared or swapped:
            self._prefix_hits += 1
            self._prefix_pages_hit += len(shared) + swapped
            pages = self._slot_pages[slot]
            for col, page in enumerate(shared):
                if self._page_ref[page] == 0:  # revive a retained page
                    del self._cached[page]
                self._page_ref[page] += 1
                self._table[slot, col] = page
                pages.append(page)
            if swapped:
                # host-tier continuation: each spilled chunk swaps back
                # into a fresh device page.  Pin the remaining host keys
                # while fetching — a fetch's own device allocation can
                # spill an evicted page, and that spill must never
                # recycle a host slot this same admission still needs.
                host_keys = match.keys[len(shared):]
                self._host_pins = set(host_keys)
                try:
                    for col, key in enumerate(host_keys, start=len(shared)):
                        self._host_pins.discard(key)
                        page = self._fetch_host(key)
                        self._page_ref[page] = 1
                        self._table[slot, col] = page
                        pages.append(page)
                finally:
                    self._host_pins = set()
            self._slot_keys[slot] = list(match.keys)
            self._table_dirty = True
        self._slot_reserved[slot] = len(shared) + swapped + tail_need
        if not lazy_tail:
            self.ensure(slot, len(tokens))
            self.register_filled(slot, tokens, len(tokens))
        elif fill_len:
            # chunked prefill: the dispatch fills [0, fill_len); its full
            # pages are registerable like any prefilled page (causal
            # attention makes their content independent of the suffix)
            self.ensure(slot, fill_len)
            self.register_filled(slot, tokens, fill_len)
        self._peak_in_use = max(self._peak_in_use, self.pages_in_use)
        return len(shared) + swapped

    def fork_need(
        self, parent_slot: int, upto_len: int, reserve_len: int
    ) -> int:
        """Pages a fork admission must be able to reserve: the worst-case
        tail beyond the shared coverage, plus one copy-on-write headroom
        page — the child's first write always lands inside the last
        shared page (it re-processes the parent's final prompt token)."""
        if self.layout != "paged":
            return 0
        shared = min(
            -(-upto_len // self.page_size), len(self._slot_pages[parent_slot])
        )
        total = self.pages_for(min(reserve_len, self.serve_cfg.max_seq_len))
        return max(total - shared, 0) + (1 if shared else 0)

    def fork(
        self, slot: int, parent_slot: int, upto_len: int, reserve_len: int
    ) -> int:
        """Map the parent's pages covering positions [0, ``upto_len``)
        onto ``slot`` with a refcount bump each — the n-best
        generation-page sharing path (``Engine.submit(n=...)``).

        Unlike a prefix-index hit, which only ever shares fully
        prompt-written pages, this shares the parent's *live* pages,
        including the page the parent is actively generating into; the
        child's own writes split off private copies through the ordinary
        copy-on-write machinery in :meth:`ensure`.  The shared chain-key
        watermark transfers too (the child's tokens match the parent's
        on every shared page), so registration stays incremental.
        Returns the number of shared pages."""
        if self.layout != "paged":
            raise RuntimeError("fork() requires the paged layout")
        need = self.fork_need(parent_slot, upto_len, reserve_len)
        if not self.can_reserve(need):
            raise RuntimeError(
                f"cannot reserve {need} KV pages for fork; check "
                "can_reserve(fork_need()) before calling fork()"
            )
        parent_pages = self._slot_pages[parent_slot]
        n = min(-(-upto_len // self.page_size), len(parent_pages))
        pages = self._slot_pages[slot]
        assert not pages, f"fork target slot {slot} already holds pages"
        for col in range(n):
            page = parent_pages[col]
            self._page_ref[page] += 1
            self._table[slot, col] = page
            pages.append(page)
        self._table_dirty = True
        self._slot_keys[slot] = list(self._slot_keys[parent_slot][:n])
        self._slot_reserved[slot] = n + need
        self._gen_pages_shared += n
        self._peak_in_use = max(self._peak_in_use, self.pages_in_use)
        return n

    def register_filled(
        self, slot: int, tokens: list[int], upto_len: int
    ) -> None:
        """Register ``slot``'s fully-written pages (positions
        [0, upto_len), token ids ``tokens``) in the prefix index so later
        same-prefix admissions can share them.  Idempotent; pages already
        registered (shared prefix pages) and keys already served by
        another live page are left untouched.  Incremental: the slot's
        chain-key watermark (``_slot_keys``) means each page is chunked
        and interned once per residency, not once per decode dispatch."""
        if not self.prefix_cache:
            return
        pages = self._slot_pages[slot]
        keys = self._slot_keys[slot]
        parent = keys[-1] if keys else 0
        for i in range(len(keys), min(upto_len // self.page_size, len(pages))):
            chunk = tuple(tokens[i * self.page_size:(i + 1) * self.page_size])
            parent = self._intern_key(parent, chunk)
            keys.append(parent)
            page = pages[i]
            if page in self._page_key or parent in self._prefix_index:
                continue
            self._prefix_index[parent] = page
            self._page_key[page] = parent

    def alloc(self, slot: int, length: int) -> None:
        """Ensure ``slot`` owns pages covering positions [0, length)."""
        self.ensure(slot, length)

    def ensure(
        self, slot: int, upto_len: int, write_from: int | None = None
    ) -> None:
        """Grow ``slot``'s page list to cover ``upto_len`` positions —
        called before each decode dispatch so mid-scan writes never cross
        into unallocated space.  When ``write_from`` is given, pages
        overlapping the write range [write_from, upto_len) are made
        privately writable first: a shared page (refcount > 1) is
        copy-on-write replaced (fresh page, device copy scheduled for
        :meth:`flush_copies`, table entry swapped), and a registered
        sole-owner page is dropped from the prefix index, so shared
        history is immutable.  Under the engine's admission discipline
        (reservation at admit()), the pool-exhausted error below is
        unreachable; it guards direct misuse of the manager."""
        if self.layout != "paged":
            if not self._slot_pages[slot]:
                self._slot_pages[slot] = [slot]
                self._allocs_total += 1
                self._peak_in_use = max(self._peak_in_use, self.pages_in_use)
            return
        pages = self._slot_pages[slot]
        need = self.pages_for(upto_len)
        while len(pages) < need:
            page = self._take_page()
            if page is None:
                raise RuntimeError(
                    f"KV page pool exhausted ({self.num_pages} pages of "
                    f"{self.page_size} tokens); raise ServeConfig.kv_pages "
                    "or admit fewer concurrent long sequences"
                )
            self._table[slot, len(pages)] = page
            pages.append(page)
            self._page_ref[page] = 1
            self._allocs_total += 1
            self._table_dirty = True
        if write_from is not None and upto_len > write_from:
            first = write_from // self.page_size
            last = (upto_len - 1) // self.page_size
            for col in range(first, min(last + 1, len(pages))):
                page = pages[col]
                if self._page_ref[page] > 1:
                    fresh = self._take_page()
                    if fresh is None:
                        raise RuntimeError(
                            "KV page pool exhausted during copy-on-write; "
                            "raise ServeConfig.kv_pages"
                        )
                    self._pending_copies.append((page, fresh))
                    self._page_ref[page] -= 1
                    self._page_ref[fresh] = 1
                    pages[col] = fresh
                    self._table[slot, col] = fresh
                    self._table_dirty = True
                    self._cow_copies += 1
                    self._allocs_total += 1
                    # the CoW headroom reserved at admission is now spent
                    self._slot_reserved[slot] = max(
                        self._slot_reserved[slot] - 1, len(pages)
                    )
                    # the chunk content diverges from the chained key
                    del self._slot_keys[slot][col:]
                elif page in self._page_key:
                    # sole owner about to mutate a registered page: the
                    # index must never serve stale content
                    self._deregister(page)
                    del self._slot_keys[slot][col:]
        self._peak_in_use = max(self._peak_in_use, self.pages_in_use)

    def free(self, slot: int) -> None:
        """Drop a finished (or preempted) slot's references immediately.
        A page whose refcount falls to zero returns to the free list —
        unless it is registered in the prefix index and prefix caching is
        on, in which case it is retained on the evictable LRU so repeated
        prompts keep hitting."""
        pages = self._slot_pages[slot]
        self._slot_pages[slot] = []
        self._slot_reserved[slot] = 0
        self._slot_keys[slot] = []
        if self.layout != "paged" or not pages:
            return
        freed: set[int] = set()
        for page in reversed(pages):
            self._page_ref[page] -= 1
            if self._page_ref[page] > 0:
                continue
            if self.prefix_cache and page in self._page_key:
                self._cached[page] = None
            else:
                self._free.append(page)
                freed.add(page)
        if freed and self._pending_copies:
            # drop queued CoW copies whose destination just returned to
            # the free list: the copy's content died with this tenancy,
            # and flushing it later would corrupt whichever unrelated
            # request reuses the page (the prefill dispatch syncs the
            # table without flushing CoW copies, so a stale copy could
            # land AFTER the page's next tenant prefilled into it)
            self._pending_copies = [
                (s, d) for s, d in self._pending_copies if d not in freed
            ]
        if freed and self._swap_in_by_page:
            # likewise cancel unflushed swap-ins aimed at freed pages —
            # the chain key (and its rows) stay fetchable in the host
            # ring, and no stale copy targets the page's next tenant
            for page in freed:
                if page in self._swap_in_by_page:
                    self._cancel_swap_in(page)
        self._table[slot, :] = TRASH_PAGE
        self._table_dirty = True

    # ------------------------------------------------------ device sync --
    def flush_copies(self, caches: PyTree) -> PyTree:
        """Apply scheduled copy-on-write page copies to the device pools.

        Host-side eager scatter of whole pool rows — it runs outside the
        engine's jitted prefill/decode programs, so the compiled program
        budget is untouched.  Must run before the decode dispatch that
        writes the copied pages (the engine calls it right after the
        per-slot :meth:`ensure` pass)."""
        if self.layout != "paged" or not self._pending_copies:
            return caches
        src = jnp.asarray([s for s, _ in self._pending_copies], jnp.int32)
        dst = jnp.asarray([d for _, d in self._pending_copies], jnp.int32)
        self._pending_copies.clear()
        layers = dict(caches["layers"])
        for name, pool in layers.items():
            if name == "page_table":
                continue
            layers[name] = pool.at[:, dst].set(pool[:, src])
        return {**caches, "layers": layers}

    def flush_swaps(self, caches: PyTree) -> PyTree:
        """Apply queued victim-tier page movement to the device pools:
        spills (evicted-but-warm device rows -> host ring) first, then
        swap-ins (host rows -> freshly allocated device pages), so a
        chain that spilled and re-matched before any dispatch moves
        device -> host -> device in one flush.  Host-side eager batched
        copies outside every jitted program — like
        :meth:`flush_copies`, the compiled program budget is untouched.
        The executor runs it at the top of every dispatch host_prep,
        BEFORE ``flush_copies``: a CoW destination may be a just-evicted
        page whose rows must reach the host ring before the copy
        overwrites them."""
        if self.layout != "paged" or not (
            self._pending_spills or self._pending_swap_ins
        ):
            return caches
        t0 = time.perf_counter()
        layers = dict(caches["layers"])
        if self._pending_spills:
            # one row per host slot — a later queue entry supersedes an
            # earlier one aimed at the same recycled slot
            by_host = {h: p for p, h in self._pending_spills}
            self._pending_spills.clear()
            hosts = list(by_host)
            pages = jnp.asarray([by_host[h] for h in hosts], jnp.int32)
            for name, pool in layers.items():
                if name == "page_table":
                    continue
                self._host_pool[name][:, hosts] = np.asarray(pool[:, pages])
        if self._pending_swap_ins:
            hosts = [h for h, _ in self._pending_swap_ins]
            dst = jnp.asarray(
                [p for _, p in self._pending_swap_ins], jnp.int32
            )
            for name, pool in layers.items():
                if name == "page_table":
                    continue
                rows = jnp.asarray(self._host_pool[name][:, hosts])
                layers[name] = pool.at[:, dst].set(rows.astype(pool.dtype))
            for host, page in self._pending_swap_ins:
                self._swap_in_by_page.pop(page, None)
                # a slot whose key was restored mid-flight (deregistered
                # target page) keeps holding the chain's rows; every
                # other slot returns to the ring's free list
                if host not in self._host_key:
                    self._host_free.append(host)
            self._pending_swap_ins.clear()
        self._swap_latency_s += time.perf_counter() - t0
        return {**caches, "layers": layers}

    def write_table(self, caches: PyTree) -> PyTree:
        """Refresh the stacked device page table from the host table
        (no-op for dense or when nothing changed since the last sync)."""
        if self.layout != "paged" or not self._table_dirty:
            return caches
        # .copy() is load-bearing: the CPU backend zero-copies aligned
        # numpy buffers, so the device table would otherwise alias the
        # live host table — an in-flight dispatch (async engine loop)
        # could then observe ``ensure``/``free`` mutations made while
        # its program is still running
        table = jnp.asarray(self._table.copy())
        stacked = jnp.broadcast_to(
            table[None], (self.cfg.n_layers,) + table.shape
        )
        if self.table_sharding is not None:
            stacked = jax.device_put(stacked, self.table_sharding)
        layers = dict(caches["layers"])
        layers["page_table"] = stacked
        self._table_dirty = False
        return {**caches, "layers": layers}

    # --------------------------------------------------- traced insert --
    def insert_prefill(
        self,
        big: PyTree,
        filled: PyTree,
        slots: jax.Array,
        shared_pages: jax.Array | None = None,
    ) -> PyTree:
        """Insert tail-masked dense prefill rows into the big caches
        (traced inside the engine's per-bucket jitted prefill).
        ``shared_pages``: per-row count of leading prefix-cache pages
        whose (recomputed, bit-identical) values must not be re-written
        — their columns scatter to the trash page instead."""
        if self.layout == "paged":
            return insert_prefill_paged(
                big, filled, slots, self.page_size, shared_pages
            )
        return insert_prefill_dense(big, filled, slots)

    # ---------------------------------------------------------- metrics --
    @property
    def pages_in_use(self) -> int:
        """Distinct live pages (a shared page counts once)."""
        if self.layout == "paged":
            return int((self._page_ref > 0).sum())
        return sum(len(p) for p in self._slot_pages)

    @property
    def pages_capacity(self) -> int:
        if self.layout == "paged":
            return self.num_pages - 1  # trash page is not allocatable
        return self.serve_cfg.max_batch

    def stats(self) -> CacheStats:
        return CacheStats(
            layout=self.layout,
            kv_bytes=self.kv_bytes,
            page_size=self.page_size,
            pages_in_use=self.pages_in_use,
            pages_capacity=self.pages_capacity,
            page_allocs_total=self._allocs_total,
            pages_in_use_peak=self._peak_in_use,
            pages_cached=len(self._cached),
            prefix_queries=self._prefix_queries,
            prefix_hits=self._prefix_hits,
            prefix_pages_hit=self._prefix_pages_hit,
            cow_copies=self._cow_copies,
            page_evictions=self._evictions,
            gen_pages_shared=self._gen_pages_shared,
            swap_outs=self._swap_outs,
            swap_ins=self._swap_ins,
            host_evictions=self._host_evictions,
            host_pages_used=self.host_pages - len(self._host_free),
            host_pages_capacity=self.host_pages,
            swap_latency_s=self._swap_latency_s,
        )

    # ------------------------------------------------------- invariants --
    def check_invariants(self) -> None:
        """Assert the paged pool's structural invariants; raises
        AssertionError with a descriptive message on any violation.  Used
        by the property-based trace tests after every operation; cheap
        enough (O(pages + table)) to call in debugging sessions too."""
        if self.layout != "paged":
            return
        ref = self._page_ref
        assert ref[TRASH_PAGE] == 0, "trash page acquired a refcount"
        assert TRASH_PAGE not in self._free, "trash page on the free list"
        assert TRASH_PAGE not in self._cached, "trash page retained as cached"
        assert TRASH_PAGE not in self._page_key, "trash page registered"
        live = {p for p in range(self.num_pages) if ref[p] > 0}
        free_set, cached_set = set(self._free), set(self._cached)
        assert len(free_set) == len(self._free), "free list holds duplicates"
        assert not (free_set & cached_set), "page both free and cached"
        assert not (free_set & live), "live page on the free list"
        assert not (cached_set & live), "live page retained as cached"
        universe = free_set | cached_set | live
        expected = set(range(self.num_pages)) - {TRASH_PAGE}
        assert universe == expected, (
            f"page leak/double-free: missing={sorted(expected - universe)} "
            f"extra={sorted(universe - expected)}"
        )
        # refcount conservation: every reference is a slot table entry
        counts = np.zeros(self.num_pages, np.int64)
        for slot, pages in enumerate(self._slot_pages):
            for col, page in enumerate(pages):
                assert page != TRASH_PAGE, f"slot {slot} maps the trash page"
                assert self._table[slot, col] == page, (
                    f"table desync at slot {slot} col {col}"
                )
                counts[page] += 1
            for col in range(len(pages), self.pages_per_slot):
                assert self._table[slot, col] == TRASH_PAGE, (
                    f"stale table entry at slot {slot} col {col}"
                )
        assert np.array_equal(counts, ref), (
            f"refcount drift: table refs {counts.nonzero()[0].tolist()} vs "
            f"refcounts {ref.nonzero()[0].tolist()}"
        )
        assert self.pages_in_use == len(live) == len(
            {p for pages in self._slot_pages for p in pages}
        ), "pages_in_use != distinct live table entries"
        for page in self._cached:
            assert page in self._page_key, "cached page lost its index key"
        for key, page in self._prefix_index.items():
            assert self._page_key.get(page) == key, (
                f"index/page key desync for page {page}"
            )
            assert page in live or page in cached_set, (
                f"prefix index maps a freed page {page}"
            )
        for page in self._page_key:
            assert page in live or page in cached_set, (
                f"registered page {page} is neither live nor cached"
            )
        for slot, (reserved, pages) in enumerate(
            zip(self._slot_reserved, self._slot_pages)
        ):
            assert reserved >= len(pages) or reserved == 0, (
                f"slot {slot} holds more pages than it reserved"
            )
            assert len(self._slot_keys[slot]) <= len(pages), (
                f"slot {slot} chain-key watermark outran its page list"
            )
        # a page shared by several slots sits at the SAME table column in
        # every owner: both sharing paths (prefix-index hits and n-best
        # forks) map leading runs of pages, so a shared page's tokens
        # occupy identical global positions in every mapping — the paged
        # gather's position arithmetic depends on this.  Covers
        # generation-page refcounts: a forked generated-into page obeys
        # the same rule until copy-on-write splits it.
        col_of: dict[int, int] = {}
        for slot, pages in enumerate(self._slot_pages):
            for col, page in enumerate(pages):
                seen = col_of.setdefault(page, col)
                assert seen == col, (
                    f"shared page {page} mapped at column {seen} and at "
                    f"column {col} (slot {slot})"
                )
        # --- host victim tier: the ring is its own page universe ---
        assert len(self._host_index) == len(self._host_key), (
            "host index/reverse-map size mismatch"
        )
        for key, host in self._host_index.items():
            assert 0 <= host < self.host_pages, (
                f"host slot {host} outside the ring"
            )
            assert self._host_key.get(host) == key, (
                f"host index/slot key desync for slot {host}"
            )
            assert key not in self._prefix_index, (
                f"chain key {key} served by both tiers"
            )
        host_free = set(self._host_free)
        assert len(host_free) == len(self._host_free), (
            "host free list holds duplicates"
        )
        held = set(self._host_key)
        transit = {h for h, _ in self._pending_swap_ins}
        assert not (host_free & held), "host slot both free and indexed"
        assert not (host_free & transit), (
            "host slot freed while its swap-in is still pending"
        )
        assert host_free | held | transit == set(range(self.host_pages)), (
            "host slot leak/double-free"
        )
        assert {p for _, p in self._pending_swap_ins} == set(
            self._swap_in_by_page
        ), "pending swap-in queue and its page map desync"
        for page, (host, _key) in self._swap_in_by_page.items():
            assert ref[page] > 0 or page in self._cached, (
                f"pending swap-in targets page {page}, neither live nor cached"
            )
        # a spill's slot holds its key, or its key was fetched back before
        # the spill flushed: flush_swaps lands spills before swap-ins, so
        # the rows pass device -> host -> device in one flush
        for page, host in self._pending_spills:
            assert host in self._host_key or host in transit, (
                f"pending spill targets unindexed host slot {host}"
            )
