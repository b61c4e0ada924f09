"""Logical-axis sharding rules (MaxText-style) for DP/FSDP/TP/EP/SP.

Every parameter leaf carries ``logical_axes`` (models/params.py).  Rules
map each logical axis to mesh axes; an axis whose size does not divide the
mesh-axis product falls back to replication (recorded, so the dry-run can
report which tensors lost their preferred sharding — hillclimb material).

Default mapping (single pod (data=16, model=16); 'pod' joins the data axes
on the multi-pod mesh):

  batch       -> (pod, data)        activations / cache batch
  embed       -> data   [FSDP]      weights' non-TP axis (ZeRO-3)
  heads/kv_heads/mlp/q_lora/kv_lora/inner -> model  [TP]
  vocab       -> model  [TP]
  experts     -> model  [EP]
  cache_len   -> None (or model under SP for long-context decode)
  layers      -> None (scan axis)
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ParallelismConfig


@dataclasses.dataclass
class ShardingRules:
    mesh: Mesh
    plan: ParallelismConfig = dataclasses.field(default_factory=ParallelismConfig)
    overrides: dict[str, tuple[str, ...] | None] = dataclasses.field(
        default_factory=dict
    )
    # populated as specs are built: leaves that fell back to replication
    fallbacks: list[tuple[str, int]] = dataclasses.field(default_factory=list)

    def _mesh_axes_for(self, logical: str | None):
        if logical is None:
            return None
        if logical in self.overrides:
            return self.overrides[logical]
        names = self.mesh.axis_names
        has_pod = "pod" in names
        batch_axes = ("pod", "data") if has_pod else ("data",)
        m = {
            "batch": batch_axes if self.plan.dp else None,
            "embed": ("data",) if self.plan.fsdp else None,
            "frontend": None,
            "heads": ("model",) if self.plan.tp else None,
            "kv_heads": ("model",) if self.plan.tp else None,
            "mlp": ("model",) if self.plan.tp else None,
            "inner": ("model",) if self.plan.tp else None,
            "q_lora": ("model",) if self.plan.tp else None,
            "kv_lora": ("model",) if self.plan.tp else None,
            "vocab": ("model",) if self.plan.tp else None,
            "experts": ("model",) if self.plan.ep else None,
            "ssm_heads": None,
            "cache_len": ("model",) if self.plan.sp else None,
            "seq": ("model",) if self.plan.sp else None,
            "layers": None,
        }
        return m.get(logical)

    def spec_for(
        self, logical_axes: tuple[str | None, ...], shape: tuple[int, ...]
    ) -> P:
        """PartitionSpec with divisibility fallback per axis."""
        if not logical_axes:
            return P()
        parts = []
        used: set[str] = set()
        for dim, (logical, size) in enumerate(zip(logical_axes, shape)):
            axes = self._mesh_axes_for(logical)
            if not axes:
                parts.append(None)
                continue
            # a mesh axis may be used at most once per spec
            axes = tuple(a for a in axes if a in self.mesh.axis_names and a not in used)
            if not axes:
                parts.append(None)
                continue
            total = int(np.prod([self.mesh.shape[a] for a in axes]))
            if size % total != 0:
                # try a prefix of the axes tuple before giving up
                ok = None
                for cut in range(len(axes) - 1, 0, -1):
                    sub = axes[:cut]
                    t = int(np.prod([self.mesh.shape[a] for a in sub]))
                    if size % t == 0:
                        ok = sub
                        break
                if ok is None:
                    self.fallbacks.append((str(logical), size))
                    parts.append(None)
                    continue
                axes = ok
            used.update(axes)
            parts.append(axes if len(axes) > 1 else axes[0])
        return P(*parts)

    def sharding_for(self, logical_axes, shape) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec_for(logical_axes, shape))

    def tree_shardings(self, abstract_tree: Any, axes_tree: Any) -> Any:
        """NamedSharding tree for (ShapeDtypeStruct tree, logical-axes tree)."""

        def _one(leaf, axes):
            return self.sharding_for(tuple(axes), leaf.shape)

        return jax.tree.map(
            _one, abstract_tree, axes_tree,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
        )

    def batch_spec(
        self,
        ndim: int,
        sharded_dims: dict[int, str] | None = None,
        shape: tuple[int, ...] | None = None,
    ) -> P:
        """Spec for an activation/batch tensor: dim 0 = batch; extra dims
        via {dim: logical} (e.g. {1: 'seq'} for sequence parallelism).
        When ``shape`` is given, axes that don't divide fall back (e.g.
        global_batch=1 decode cells replicate the batch dim)."""
        names = self.mesh.axis_names
        batch_axes = ("pod", "data") if "pod" in names else ("data",)
        parts: list = [batch_axes if len(batch_axes) > 1 else batch_axes[0]]
        parts += [None] * (ndim - 1)
        for dim, logical in (sharded_dims or {}).items():
            axes = self._mesh_axes_for(logical)
            if axes:
                parts[dim] = axes if len(axes) > 1 else axes[0]
        if shape is not None:
            for dim in range(ndim):
                part = parts[dim]
                if part is None:
                    continue
                axes = part if isinstance(part, tuple) else (part,)
                total = int(np.prod([self.mesh.shape[a] for a in axes]))
                while axes and shape[dim] % total != 0:
                    axes = axes[:-1]
                    total = int(
                        np.prod([self.mesh.shape[a] for a in axes])
                    ) if axes else 1
                parts[dim] = (
                    None if not axes else (axes if len(axes) > 1 else axes[0])
                )
        return P(*parts)

    def batch_sharding(self, ndim: int, sharded_dims=None, shape=None) -> NamedSharding:
        return NamedSharding(self.mesh, self.batch_spec(ndim, sharded_dims, shape))


def param_shardings(rules: ShardingRules, cfg, model_module) -> Any:
    """Sharding tree for a model's parameters."""
    from repro.models import params as params_lib

    spec = model_module.param_spec(cfg)
    abstract = params_lib.abstract_params(spec)
    axes = params_lib.logical_axes(spec)
    return rules.tree_shardings(abstract, axes)


def cache_shardings(
    rules: ShardingRules,
    cfg,
    batch: int,
    max_len: int,
    quantized: bool = False,
    layout: str = "dense",
    **layout_kw,
) -> Any:
    """Sharding tree for decode caches (serve/kv_cache.cache_logical_axes).

    ``layout`` selects the KV storage layout: dense slabs shard the batch
    and cache_len axes; paged pools shard over kv_heads (TP) with the page
    axis replicated and the page table sharded over batch.  Extra
    ``layout_kw`` (page_size/num_pages) are forwarded to the spec builder.
    """
    from repro.serve import kv_cache

    abstract = kv_cache.abstract_caches(
        cfg, batch, max_len, quantized=quantized, layout=layout, **layout_kw
    )
    axes_map = kv_cache.cache_logical_axes(
        cfg, quantized=quantized, layout=layout
    )

    def _walk(abs_node, axes_node):
        if isinstance(abs_node, jax.ShapeDtypeStruct):
            return rules.sharding_for(tuple(axes_node), abs_node.shape)
        return {k: _walk(abs_node[k], axes_node[k]) for k in abs_node}

    return _walk(abstract, axes_map)


def committed_devices(tree: Any) -> list:
    """The devices ``tree``'s arrays are committed to, in id order."""
    devs = set()
    for x in jax.tree.leaves(tree):
        if isinstance(x, jax.Array):
            devs |= x.sharding.device_set
    return sorted(devs, key=lambda d: d.id)


def bytes_per_device(tree: Any) -> int:
    """The most bytes of ``tree``'s arrays that any one device holds."""
    per: dict = {}
    for x in jax.tree.leaves(tree):
        if not isinstance(x, jax.Array):
            continue
        n = math.prod(x.sharding.shard_shape(x.shape)) * x.dtype.itemsize
        for d in x.sharding.device_set:
            per[d] = per.get(d, 0) + n
    return max(per.values(), default=0)
