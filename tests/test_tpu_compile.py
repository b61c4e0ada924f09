"""Compile the main-path Pallas kernels for a described TPU v5e chip at
minicpm-2b widths (d_model 2304, 36 heads of 64, d_ff 5760), and the
engine's decode scan and extend program at each benchmark cell's widths.

Nothing runs: the TPU compiler is handed shapes for a chip that is
described, not attached, and refuses what Mosaic would refuse on the
chip (unsupported shape casts, misaligned slices, too much VMEM) —
faults that interpret mode cannot see.  The topology is described inside
a fixture, never at import: only one process may load the TPU library,
and every test worker imports this file.
"""

import json
import os
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import mha
from repro.kernels.layernorm import layernorm
from repro.kernels.lut_softmax import lut_softmax
from repro.kernels.qmatmul import qmatmul

D_MODEL, N_HEADS, HEAD_DIM, D_FF = 2304, 36, 64, 5760
BATCH, SEQ = 4, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _hlo(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("mode", ["safe", "lut"])
def test_flash_attention_compiles(one_chip, mode):
    qkv = jax.ShapeDtypeStruct(
        (BATCH, N_HEADS, SEQ, HEAD_DIM), jnp.bfloat16, sharding=one_chip
    )
    hlo = _hlo(
        lambda q, k, v: mha(q, k, v, causal=True, mode=mode, use_pallas=True),
        qkv, qkv, qkv,
    )
    assert "tpu_custom_call" in hlo


def test_lut_softmax_compiles(one_chip):
    scores = jax.ShapeDtypeStruct(
        (N_HEADS * 128, SEQ), jnp.float32, sharding=one_chip
    )
    assert "tpu_custom_call" in _hlo(lut_softmax, scores)


@pytest.mark.parametrize("use_lut", [False, True])
def test_rmsnorm_compiles(one_chip, use_lut):
    x = jax.ShapeDtypeStruct((BATCH * SEQ, D_MODEL), jnp.bfloat16,
                             sharding=one_chip)
    g = jax.ShapeDtypeStruct((D_MODEL,), jnp.float32, sharding=one_chip)
    hlo = _hlo(lambda x, g: layernorm(x, g, rms=True, use_lut=use_lut), x, g)
    assert "tpu_custom_call" in hlo


def test_qmatmul_compiles(one_chip):
    x = jax.ShapeDtypeStruct((SEQ, D_MODEL), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((D_MODEL, D_FF), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _hlo(qmatmul, x, w)


# ------------------------------------------- the KV pool is updated in place --

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "bench" / "configs"

#: ops that only pass a buffer along, and the in-place token write
_POOL_OPS_ALLOWED = {"parameter", "get-tuple-element", "tuple", "bitcast",
                     "scatter"}
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\((.*)$"
)
_CALLS = re.compile(r"calls=%([^,\s]+)")


def _computations(hlo: str) -> dict[str, list[tuple[str, tuple, str, str]]]:
    """computation name -> its instructions (name, shape, opcode, rest)."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
            continue
        m = _INSTRUCTION.match(line)
        if m and cur is not None:
            shape = tuple(int(x) for x in m.group(2).split(",") if x)
            cur.append((m.group(1), shape, m.group(3), m.group(4)))
    return comps


def _pool_producers(hlo: str, pool_shapes: set) -> list[str]:
    """Ops outside fused computations whose result is shaped like a whole
    stacked pool or one layer's pool, other than pass-through ops and the
    in-place scatter (a fusion counts as the op at its root)."""
    comps = _computations(hlo)
    root_op = {name: ins[-1][2] for name, ins in comps.items() if ins}
    fused = {}  # fusion instruction -> the computation it calls
    for instructions in comps.values():
        for name, _, op, rest in instructions:
            called = _CALLS.search(rest)
            if op == "fusion" and called:
                fused[name] = called.group(1)
    found = []
    for comp, instructions in comps.items():
        if comp in fused.values():
            continue
        for name, shape, op, _ in instructions:
            if shape not in pool_shapes:
                continue
            op = root_op.get(fused.get(name), op)
            if op not in _POOL_OPS_ALLOWED:
                found.append(f"{comp}: %{name} {op} {list(shape)}")
    return found


@pytest.mark.parametrize("name", ["minicpm-2b", "granite-8b-l18"])
def test_kv_pool_is_updated_in_place(one_chip, monkeypatch, name):
    """The decode scan and the extend program write tokens into the stacked
    pool and read pages from it where it lies: no op copies, slices out or
    writes back a whole pool or a whole layer's pool, the pool enters in
    the layout the program works in, and the output aliases it (donation).
    Each cell's engine at 2 of its layers, pool at its own page count."""
    from bench import harness
    from repro.models import lm
    from repro.serve import kv_cache
    from repro.serve.executor import ModelExecutor

    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    cfg["model"]["num_hidden_layers"] = 2
    mc = harness.model_config(cfg)
    sc = harness.serve_config(cfg)
    # shapes only: nothing is allocated for a chip that is only described
    monkeypatch.setattr(
        kv_cache.CacheManager, "init_device_caches",
        lambda self: self._abstract(),
    )
    ex = ModelExecutor(mc, lm.abstract_params(mc), sc)

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree,
        )

    params, caches = on_chip(ex.params), on_chip(ex.caches)
    pools = [
        leaf for key, leaf in caches["layers"].items() if key != "page_table"
    ]
    pool_shapes = {p.shape for p in pools} | {p.shape[1:] for p in pools}
    pool_bytes = sum(int(np.prod(p.shape)) * p.dtype.itemsize for p in pools)

    nb, steps, w = sc.max_batch, sc.decode_steps, ex.extend_width
    i32, f32 = jnp.int32, jnp.float32

    def arg(shape, dtype=i32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    key = arg((2,), jnp.uint32)
    decode = ex._decode_fn.lower(
        params, arg((nb,)), arg((nb,)), arg((nb,), bool), arg((nb,)),
        arg((nb,)), arg((nb,), f32), arg((nb,)), arg((nb,), f32),
        arg((nb,)), arg((steps, nb)), arg((nb,)), caches, key,
    ).compile()
    extend = ex._extend_fn.lower(
        params, arg((nb, w)), arg((nb,)), arg((nb,)), caches
    ).compile()
    for program in (decode, extend):
        assert _pool_producers(program.as_text(), pool_shapes) == []
        assert program.memory_analysis().alias_size_in_bytes >= pool_bytes


def test_tensor_parallel_decode_on_a_v5e_2x2(topo, monkeypatch):
    """granite-8b's decode scan as ``shard_decode`` places it over the four
    chips of a described v5e:2x2 (tensor parallel, mesh data 1 x model 4),
    at 2 of its 36 layers: each chip's operands are a quarter of the
    weights and of the KV pool, no all-gather returns a weight or one layer
    of it, and the layer loop holds two all-reduces (attention output, MLP
    down projection)."""
    from bench import harness
    from repro.distributed import sharding
    from repro.launch.mesh import tensor_parallel_mesh
    from repro.models import lm
    from repro.serve import kv_cache
    from repro.serve.executor import ModelExecutor

    cfg = json.loads((CONFIGS / "granite-8b.json").read_text())
    cfg["model"]["num_hidden_layers"] = 2
    mc = harness.model_config(cfg)
    sc = harness.serve_config(cfg, shard_decode=False)
    monkeypatch.setattr(
        kv_cache.CacheManager, "init_device_caches",
        lambda self, shardings=None: self._abstract(),
    )
    ex = ModelExecutor(mc, lm.abstract_params(mc), sc)
    mesh = tensor_parallel_mesh(topo.devices)
    rules = sharding.ShardingRules(mesh)
    psh = sharding.param_shardings(rules, mc, lm)
    csh = ex.cache_mgr.device_shardings(rules)
    assert rules.fallbacks == []
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    def placed(tree, shardings):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, shardings,
        )

    params, caches = placed(ex.params, psh), placed(ex.caches, csh)
    nb, steps, i32, f32 = sc.max_batch, sc.decode_steps, jnp.int32, jnp.float32

    def arg(shape, dtype=i32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    decode = jax.jit(
        ex._decode_scan, out_shardings=(rep,) * 6 + (csh,), donate_argnums=12
    ).lower(
        params, arg((nb,)), arg((nb,)), arg((nb,), bool), arg((nb,)),
        arg((nb,)), arg((nb,), f32), arg((nb,)), arg((nb,), f32),
        arg((nb,)), arg((steps, nb)), arg((nb,)), caches,
        arg((2,), jnp.uint32),
    ).compile()

    def nbytes(tree):
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in jax.tree.leaves(tree))

    whole = nbytes(params) + nbytes(caches)
    assert decode.memory_analysis().argument_size_in_bytes < whole / 3

    hlo = decode.as_text()
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif cur is not None:
            cur.append(line)
    weights = {tuple(s) for a in jax.tree.leaves(params)
               for s in (a.shape, a.shape[1:]) if len(s) >= 2}
    gathered = {
        tuple(int(x) for x in m.group(1).split(",") if x)
        for m in re.finditer(r"= \w+\[([\d,]*)\]\S* all-gather(?:-start)?\(", hlo)
    }
    assert not gathered & weights, sorted(gathered & weights)
    loops = [comps[b] for b in set(re.findall(r"body=%([^,\s]+)", hlo))]
    layer_loops = [
        body for body in loops
        if not any(" while(" in line for line in body)
        and any(re.search(r" all-reduce(-start)?\(", line) for line in body)
    ]
    assert len(layer_loops) == 1
    reduces = [line for line in layer_loops[0] if re.search(r" all-reduce(-start)?\(", line)]
    assert len(reduces) == 2, reduces
