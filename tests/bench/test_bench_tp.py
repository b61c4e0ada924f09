"""CPU tests of tensor-parallel serving on four host devices, in one child
process (the one of ``test_bench_mesh.py``): a tiny GQA configuration whose
heads, KV heads, FFN columns and vocabulary split four ways, drawn split over
the four devices and served through ``Engine`` with ``shard_decode``.

What the child reports: the engine's mesh and where each KV pool leaf and
weight lies; the reference's gaps for what the engine served (prefill, then
decode through the cache) and the ``w8a8`` control's; and the collectives of
the compiled decode program."""

from __future__ import annotations

import json
from pathlib import Path

import pytest
from test_bench_mesh import SEED, _run

CONFIG = Path(__file__).resolve().parents[2] / "bench" / "testdata" / "configs" / "tiny-tp.json"

SCRIPT = f"""
import json, re
import jax, numpy as np
from bench import harness, weights
from bench.arch import llama
from repro.serve import Engine

cfg = json.loads(open({str(CONFIG)!r}).read())
mc = harness.model_config(cfg)
m = llama.dims(mc)
place = weights.Place(jax.devices())
w = weights.make(llama, m, {SEED}, cfg["weights"]["embed_std"], mc.dtype, place)
sc = harness.serve_config(cfg)
eng = Engine(mc, llama.program_tree(w), sc)
ex = eng.executor

# the decode program's operands as the dispatches hand them over
calls = []
decode = ex._decode_fn
def record(*args):
    calls.append(jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=x.sharding if isinstance(
            x.sharding, jax.sharding.NamedSharding) else None)
        if isinstance(x, jax.Array) else x, args))
    return decode(*args)
ex._decode_fn = record

rng = np.random.default_rng(0)
prompts = [list(map(int, rng.integers(0, m.vocab, n))) for n in (3, 7, 12, 20, 30, 40, 9, 17)]
handles = [eng.submit(p, max_new_tokens=24) for p in prompts]
eng.generate()
seqs = [(p, list(eng.request(h).generated)) for p, h in zip(prompts, handles)]
gaps = llama.logit_gaps(m, w, seqs, sc.max_seq_len, place=place)
control = llama.logit_gaps(m, w, seqs, sc.max_seq_len, "w8a8", place)

hlo = decode.lower(*calls[0]).compile().as_text()
comps, cur = {{}}, None
for line in hlo.splitlines():
    head = re.match(r"^(?:ENTRY )?%(\\S+) .*\\{{$", line)
    if head:
        cur = comps.setdefault(head.group(1), [])
    elif cur is not None:
        cur.append(line)
def ops(lines, op):
    return [re.search(r"= \\(?(\\w+\\[[\\d,]*\\])", l).group(1) for l in lines
            if re.search(r" " + op + r"(-start)?\\(", l)]
bodies = set(re.findall(r"body=%([^,\\s]+)", hlo))
loops = [{{"all_reduce": ops(comps[b], "all-reduce"),
          "nested": sum(" while(" in l for l in comps[b])}} for b in bodies]
weight_shapes = {{str(s) for x in jax.tree.leaves(ex.params)
                 for s in (x.shape, x.shape[1:]) if len(s) >= 2}}
gathered = [s for lines in comps.values() for s in ops(lines, "all-gather")]

pool = ex.caches["layers"]["k"]
print(json.dumps({{
    "mesh": dict(ex.mesh.shape),
    "pool": [list(pool.shape), [list(s.data.shape) for s in pool.addressable_shards]],
    "pool_devices": len(pool.sharding.device_set),
    "tel": {{k: ex.tel[k] for k in ("param_bytes_per_chip", "kv_pool_bytes_per_chip",
                                   "replicated_param_leaves")}},
    "param_bytes": sum(x.nbytes for x in jax.tree.leaves(ex.params)),
    "cache_bytes": sum(x.nbytes for x in jax.tree.leaves(ex.caches)),
    "table_bytes": ex.caches["layers"]["page_table"].nbytes,
    "served": sum(len(s) for _, s in seqs),
    "gap": max(float(g.max()) for g in gaps),
    "control_gap": max(float(g.max()) for g in control),
    "loops": loops,
    "gathered": [re.sub(r"^\\w+", "", s) for s in gathered],
    "weight_shapes": sorted("[" + s.strip("()").replace(" ", "").rstrip(",") + "]"
                            for s in weight_shapes),
}}))
"""


@pytest.fixture(scope="module")
def served() -> dict:
    return _run(SCRIPT)


def test_tensor_parallel_placement_splits_kv_heads_over_four_chips(served):
    """The engine places a (data 1, model 4) mesh over the four devices the
    weights were drawn on; each chip's KV pool leaf holds kv_heads / 4 of
    every page, and its telemetry counts a quarter of the pool and under a
    third of the weights on the fullest chip, with no weight replicated."""
    cfg = json.loads(CONFIG.read_text())["model"]
    assert served["mesh"] == {"data": 1, "model": 4}
    shape, shards = served["pool"]
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    assert shape[-1] == cfg["num_key_value_heads"] * hd
    assert served["pool_devices"] == 4 and len(shards) == 4
    for s in shards:
        assert s == shape[:-1] + [cfg["num_key_value_heads"] // 4 * hd]
    tel = served["tel"]
    assert tel["replicated_param_leaves"] == 0
    pool_bytes = served["cache_bytes"] - served["table_bytes"]
    assert tel["kv_pool_bytes_per_chip"] == pool_bytes // 4 + served["table_bytes"]
    assert tel["param_bytes_per_chip"] < served["param_bytes"] / 3


def test_tensor_parallel_prefill_and_decode_match_the_reference(served):
    """Prompts of 3-40 tokens prefilled (both buckets) and 24 tokens each
    decoded through the split pool on four devices: every served token's
    float32 reference logit lies within the configuration's stated gap of
    the reference's best; the w8a8 control on the same tokens does not."""
    limit = json.loads(CONFIG.read_text())["checks"]["max_logit_gap"]
    assert served["served"] == 8 * 24
    assert served["gap"] <= limit, served["gap"]
    assert served["control_gap"] > limit, served["control_gap"]


def test_tensor_parallel_decode_gathers_no_weight(served):
    """The compiled decode program on four devices gathers no weight (no
    all-gather returns a weight's shape or one layer of it), and its layer
    loop holds two all-reduces: the attention output and the MLP down
    projection.  The all-reduces left outside it are the embedding
    lookup's and the LM head's."""
    assert not set(served["gathered"]) & set(served["weight_shapes"]), served["gathered"]
    inner = [lp["all_reduce"] for lp in served["loops"] if not lp["nested"] and lp["all_reduce"]]
    assert len(inner) == 1, served["loops"]
    assert len(inner[0]) == 2, inner
