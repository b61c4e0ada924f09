"""KV-cache subsystem tests: dense-vs-paged token parity across GQA / MLA /
int8-KV configs, slot churn (admit/finish/re-admit) with page reuse and no
cross-request leakage, the jit program budget (len(prefill_buckets) prefill
+ 1 decode, both layouts), CacheManager allocation bookkeeping,
sharding composition for paged pools, and every program consuming
(donating) the pool it is handed."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import ServeConfig
from repro.core import precision as P
from repro.models import lm
from repro.serve import CacheManager, Engine, ServingEngine
from repro.serve import kv_cache as kvc

KEY = jax.random.PRNGKey(11)

KV8 = P.PrecisionPolicy(
    "kv8", (P.Rule("kv_cache", P.int8(per_channel=False)),)
)


def _params(cfg):
    return lm.init_params(cfg, KEY)


def _serve(layout, **kw):
    base = dict(max_batch=2, max_seq_len=64, kv_layout=layout,
                kv_page_size=8, decode_steps=3)
    base.update(kw)
    return ServeConfig(**base)


def _generate(cfg, params, serve_cfg, prompts, n_new=6, seed=0):
    eng = ServingEngine(cfg, params, serve_cfg, seed=seed)
    uids = [eng.submit(list(p), n_new) for p in prompts]
    res = eng.run()
    return eng, [res[u].generated for u in uids]


PROMPTS = ([5, 9, 3, 7], [11, 2, 6], [1, 2, 3, 4, 5, 6, 7, 8, 9], [4, 4])


# ----------------------------------------------------- dense/paged parity --


@pytest.mark.parametrize(
    "arch,policy",
    [
        ("granite-8b", None),         # GQA float
        ("minicpm3-4b", None),        # MLA float
        ("granite-8b", KV8),          # GQA int8 KV (per-page scales)
        ("minicpm3-4b", KV8),         # MLA int8 latent (per-page scales)
        ("granite-8b", "int8_serve"), # full serving performance path
    ],
)
@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_dense_paged_token_identical(arch, policy, temperature):
    """Same prompts, same sampling key -> identical tokens across layouts.

    The paged gather reconstructs the exact dense logical view (same
    shape, same values at every valid position, masked elsewhere), so
    even stochastic sampling must agree token-for-token."""
    cfg = configs.get_config(arch, reduced=True)
    params = _params(cfg)
    outs = {}
    for layout in ("dense", "paged"):
        eng, outs[layout] = _generate(
            cfg, params,
            _serve(layout, policy=policy, temperature=temperature,
                   max_batch=2),
            PROMPTS,
        )
        assert eng.kv_layout == layout
    assert outs["dense"] == outs["paged"]


def _dispatches(tel):
    """Program dispatches that take the KV pool."""
    return (
        tel["prefill_dispatches"] + tel["extend_dispatches"]
        + tel["decode_dispatches"] + tel["spec_dispatches"]
    )


def _waves(cfg, params, serve_cfg, waves, n_new=6):
    eng = Engine(cfg, params, serve_cfg, seed=0)
    outs = []
    for wave in waves:
        handles = [eng.submit(list(p), max_new_tokens=n_new) for p in wave]
        fin = eng.generate()
        outs.extend(fin[h.uid].generated for h in handles)
    return eng, outs


def test_dense_paged_token_identical_through_cow_and_swaps():
    """The parity prompts again, then four tenants whose 3-page preambles
    outgrow a 12-page pool (evicted pages spill to the host tier and swap
    back on the next visit), then exact repeats of a preamble (a
    full-coverage hit whose first decode write copies the shared page).
    Every program consumes the pool it is handed and the copies and swaps
    land between dispatches, yet the paged streams equal the dense ones."""
    cfg = configs.get_config("granite-8b", reduced=True)
    params = _params(cfg)
    rng = np.random.default_rng(3)
    tenants = [list(rng.integers(0, cfg.vocab_size, 24)) for _ in range(4)]
    waves = [PROMPTS[:2], PROMPTS[2:]]
    waves += [
        [tenants[(2 * w + j) % 4] + [w + 1, j + 1] for j in range(2)]
        for w in range(6)
    ]
    waves += [[tenants[1]] * 2, [tenants[3]] * 2]
    _, dense = _waves(cfg, params, _serve("dense"), waves)
    eng, paged = _waves(
        cfg, params,
        _serve("paged", kv_pages=13, kv_prefix_cache=True,
               kv_preemption=True, kv_host_pages=32),
        waves,
    )
    assert paged == dense
    tel = eng.telemetry
    assert tel["cow_copies"] > 0 and tel["swap_ins"] > 0
    assert tel["kv_pool_donated"] == _dispatches(tel)
    eng.executor.cache_mgr.check_invariants()


@pytest.mark.parametrize("policy", [None, KV8])
@pytest.mark.parametrize("async_loop", [False, True])
def test_every_dispatch_consumes_the_kv_pool(policy, async_loop):
    """Each program takes the pool it is handed (donation) and the engine
    keeps only the program's output: after every step that dispatched,
    the pool the step started from is deleted, and ``kv_pool_donated``
    counts every prefill, extend and decode dispatch.  Repeated prompts
    hit the prefix cache; on the int8-KV datapath their tails replay
    through the extend program."""
    cfg = configs.get_config("granite-8b", reduced=True)
    eng = Engine(cfg, _params(cfg), _serve(
        "paged", policy=policy, async_loop=async_loop,
        kv_prefix_cache=True, prefill_buckets=(8, 16),
    ))
    for p in PROMPTS + PROMPTS:
        eng.submit(list(p), max_new_tokens=6)
    while eng.has_work:
        pool = kvc.pool_leaf(eng.executor.caches)
        before = _dispatches(eng.telemetry)
        eng.step()
        if _dispatches(eng.telemetry) > before:
            assert pool.is_deleted()
    tel = eng.telemetry
    assert tel["prefill_dispatches"] > 0 and tel["decode_dispatches"] > 0
    assert tel["extend_dispatches"] > 0 or policy is None
    assert tel["kv_pool_donated"] == _dispatches(tel)


def test_unpageable_families_fall_back_to_dense():
    for arch in ("mamba2-130m", "starcoder2-7b", "zamba2-1.2b"):
        cfg = configs.get_config(arch, reduced=True)
        params = _params(cfg)
        eng, out_p = _generate(cfg, params, _serve("paged"), PROMPTS[:2])
        assert eng.kv_layout == "dense"  # silent, documented fallback
        _, out_d = _generate(cfg, params, _serve("dense"), PROMPTS[:2])
        assert out_p == out_d


# --------------------------------------------------------- slot churn -----


def test_slot_churn_reuses_pages_without_leakage():
    """Admit / finish / re-admit waves through a pool smaller than the
    total page demand: freed pages must be recycled, and every wave's
    tokens must match the dense engine run through the identical
    admission sequence (no cross-request contamination from reused
    pages)."""
    cfg = configs.get_config("granite-8b", reduced=True)
    params = _params(cfg)
    waves = [PROMPTS[:2], PROMPTS[2:], ([7, 7, 7], [9, 1, 9, 1])]

    def churn(layout):
        sc = _serve(layout, max_batch=2, max_seq_len=32, kv_page_size=8)
        eng = ServingEngine(cfg, params, sc)
        outs = []
        for wave in waves:
            uids = [eng.submit(list(p), 8) for p in wave]
            res = eng.run()
            outs.append([res[u].generated for u in uids])
        return eng, outs

    eng_d, outs_d = churn("dense")
    eng_p, outs_p = churn("paged")
    assert outs_d == outs_p
    st = eng_p.cache_mgr.stats()
    # all six requests finished -> every page is back in the free list
    assert st.pages_in_use == 0
    # the pool (2 slots x 4 pages) is smaller than total demand
    # (6 requests x >=2 pages each): allocations must have recycled pages
    assert st.page_allocs_total > st.pages_capacity >= st.pages_in_use_peak


def test_oversubscribed_pool_serves_fifo_without_leakage():
    """A pool too small for two concurrently-growing sequences must
    serialize them (admission reserves prompt + generation budget, so
    decode growth never exhausts the pool mid-run) and still match the
    dense engine's output."""
    cfg = configs.get_config("granite-8b", reduced=True)
    params = _params(cfg)
    prompts = (PROMPTS[0], PROMPTS[1], [8, 6, 7], [5, 3, 0, 9])
    # 3 usable pages of 8 tokens; each request reserves
    # ceil((4 + 12) / 8) = 2 pages -> only one sequence resident at a time
    sc_p = _serve("paged", max_batch=2, max_seq_len=32, kv_page_size=8,
                  kv_pages=4)
    eng, out_p = _generate(cfg, params, sc_p, prompts, n_new=12)
    assert eng.kv_layout == "paged"
    assert eng.cache_mgr.stats().pages_in_use_peak <= 3  # never overflows
    _, out_d = _generate(
        cfg, params, _serve("dense", max_batch=2, max_seq_len=32), prompts,
        n_new=12,
    )
    # dense runs both slots concurrently, paged serializes; greedy decode
    # makes per-request tokens independent of co-residency
    assert out_p == out_d


def test_decode_growth_never_exhausts_pool():
    """Regression: short prompts with long generation budgets on a tight
    pool used to crash mid-decode with 'pool exhausted'; reservation at
    admission now serializes them instead."""
    cfg = configs.get_config("granite-8b", reduced=True)
    params = _params(cfg)
    sc = _serve("paged", max_batch=2, max_seq_len=64, kv_page_size=16,
                kv_pages=5)  # 4 usable pages; each request reserves 4
    eng = ServingEngine(cfg, params, sc)
    uids = [eng.submit([7, 8, 9], 56), eng.submit([1, 2, 3], 56)]
    res = eng.run()
    assert sorted(res) == sorted(uids)
    assert all(len(res[u].generated) == 56 for u in uids)


# ------------------------------------------------------ program budget ----


def _program_count(fn):
    size = getattr(fn, "_cache_size", None)
    return size() if callable(size) else 1


@pytest.mark.parametrize(
    "layout,features",
    [
        ("dense", {}),
        ("paged", {}),
        ("paged", {"kv_prefix_cache": True, "kv_preemption": True}),
    ],
)
def test_jit_program_budget(layout, features):
    """len(prefill_buckets) prefill programs + 1 decode program, enforced
    on the actual jit caches — for both layouts, and with the prefix
    cache + preemption knobs on (sharing/preemption are host-side
    block-table operations and must not grow the program set)."""
    cfg = configs.get_config("granite-8b", reduced=True)
    params = _params(cfg)
    rng = np.random.default_rng(0)
    prompts = [
        list(rng.integers(0, cfg.vocab_size, n))
        for n in (3, 4, 5, 6, 9, 11, 13, 15)
    ]
    sc = _serve(layout, max_batch=4, prefill_buckets=(4, 8, 16), **features)
    eng, _ = _generate(cfg, params, sc, prompts)
    assert eng.telemetry["prefill_compiles"] <= len(eng.prefill_buckets)
    assert eng.telemetry["decode_compiles"] == 1
    total_prefill = sum(
        _program_count(fn) for fn in eng._prefill_fn.values()
    )
    assert total_prefill <= len(eng.prefill_buckets)
    assert _program_count(eng._decode_fn) == 1


# ------------------------------------------------------- CacheManager -----


def test_manager_page_bookkeeping():
    cfg = configs.get_config("granite-8b", reduced=True)
    sc = ServeConfig(max_batch=2, max_seq_len=32, kv_layout="paged",
                     kv_page_size=8, kv_pages=8)
    mgr = CacheManager(cfg, sc)
    assert mgr.layout == "paged"
    assert mgr.pages_per_slot == 4 and mgr.pages_capacity == 7
    assert mgr.pages_for(1) == 1 and mgr.pages_for(8) == 1
    assert mgr.pages_for(9) == 2 and mgr.pages_for(32) == 4
    mgr.alloc(0, 9)
    assert mgr.pages_in_use == 2
    assert np.all(mgr._table[0, :2] > 0)  # page 0 is the trash page
    mgr.ensure(0, 17)
    assert mgr.pages_in_use == 3
    mgr.ensure(0, 17)  # idempotent
    assert mgr.pages_in_use == 3
    mgr.alloc(1, 30)
    assert mgr.pages_in_use == 7
    used = set(mgr._table[mgr._table > 0].tolist())
    assert len(used) == 7  # no page is shared between slots
    mgr.free(0)
    assert mgr.pages_in_use == 4
    assert np.all(mgr._table[0] == kvc.TRASH_PAGE)
    mgr.alloc(0, 24)  # freed pages are reusable
    assert mgr.pages_in_use == 7
    with pytest.raises(RuntimeError, match="exhausted"):
        mgr.ensure(0, 32)  # would need a 4th page with the pool drained


def test_manager_validates_page_size_and_pool():
    cfg = configs.get_config("granite-8b", reduced=True)
    with pytest.raises(ValueError, match="divide"):
        CacheManager(cfg, ServeConfig(max_seq_len=100, kv_layout="paged",
                                      kv_page_size=16))
    with pytest.raises(ValueError, match="kv_pages"):
        CacheManager(cfg, ServeConfig(max_seq_len=64, kv_layout="paged",
                                      kv_page_size=16, kv_pages=1))
    with pytest.raises(ValueError, match="kv_layout"):
        CacheManager(cfg, ServeConfig(kv_layout="interleaved"))


def test_engine_rejects_prompt_larger_than_pool():
    cfg = configs.get_config("granite-8b", reduced=True)
    params = _params(cfg)
    sc = _serve("paged", max_batch=2, max_seq_len=32, kv_page_size=8,
                kv_pages=3)  # 2 usable pages = 16 tokens
    eng = ServingEngine(cfg, params, sc)
    with pytest.raises(ValueError, match="pool"):
        eng.submit(list(range(1, 20)), 2)


def test_paged_cache_bytes_shrink_with_pool():
    """The point of paging: device bytes scale with the page pool, not
    with max_batch x max_seq_len."""
    cfg = configs.get_config("granite-8b", reduced=True)
    dense = CacheManager(cfg, ServeConfig(max_batch=8, max_seq_len=512))
    paged = CacheManager(
        cfg, ServeConfig(max_batch=8, max_seq_len=512, kv_layout="paged",
                         kv_page_size=32, kv_pages=33),  # 1/4 of dense
    )
    assert paged.kv_bytes < dense.kv_bytes / 3
    st = paged.stats().as_dict()
    assert st["kv_layout"] == "paged" and st["pages_capacity"] == 32


# ----------------------------------------------------------- specs --------


def test_paged_spec_shapes_gqa_and_mla():
    gqa = configs.get_config("granite-8b", reduced=True)
    spec = kvc.attention_cache_spec(
        gqa, batch=4, max_len=64, quantized=True, layout="paged",
        page_size=16, num_pages=9,
    )
    hd = gqa.resolved_head_dim
    assert spec["k"].shape == (9, 16, gqa.n_kv_heads * hd)
    assert spec["k"].dtype == jnp.int8
    assert spec["k_scale"].shape == (9, 16, gqa.n_kv_heads)
    assert spec["page_table"].shape == (4, 4)
    mla = configs.get_config("minicpm3-4b", reduced=True)
    spec = kvc.attention_cache_spec(
        mla, batch=2, max_len=64, layout="paged", page_size=16, num_pages=9
    )
    width = mla.mla.kv_lora_rank + mla.mla.qk_rope_head_dim
    assert spec["latent"].shape == (9, 16, width)
    assert spec["page_table"].shape == (2, 4)


def test_paged_spec_rejects_unpageable():
    win = configs.get_config("starcoder2-7b", reduced=True)
    with pytest.raises(ValueError, match="sliding-window"):
        kvc.attention_cache_spec(
            win, 2, 64, layout="paged", page_size=16, num_pages=9
        )
    ssm = configs.get_config("mamba2-130m", reduced=True)
    with pytest.raises(ValueError, match="position-addressed"):
        kvc.attention_cache_spec(
            ssm, 2, 64, layout="paged", page_size=16, num_pages=9
        )


def test_paged_roundtrip_write_view():
    """paged_decode_write then paged_decode_view reads back exactly what
    was written at each slot's logical position."""
    cfg = configs.get_config("granite-8b", reduced=True)
    cache = kvc.init_attention_cache(
        cfg, batch=2, max_len=32, dtype=jnp.float32, layout="paged",
        page_size=8, num_pages=9,
    )
    # slot 0 -> pages 1,2; slot 1 -> pages 3,4
    cache["page_table"] = jnp.asarray([[1, 2, 0, 0], [3, 4, 0, 0]], jnp.int32)
    rng = np.random.default_rng(0)
    hd = cfg.resolved_head_dim
    k_new = jnp.asarray(
        rng.normal(size=(2, cfg.n_kv_heads, hd)), jnp.float32
    )
    v_new = jnp.asarray(
        rng.normal(size=(2, cfg.n_kv_heads, hd)), jnp.float32
    )
    positions = jnp.asarray([3, 11], jnp.int32)  # page 0 off 3 / page 1 off 3
    cache = kvc.paged_decode_write(
        cache, {"k": k_new, "v": v_new}, positions
    )
    view = kvc.paged_decode_view(cache, cfg.n_kv_heads)
    assert view["k"].shape == (2, cfg.n_kv_heads, 32, hd)
    np.testing.assert_array_equal(np.asarray(view["k"][0, :, 3]), k_new[0])
    np.testing.assert_array_equal(np.asarray(view["k"][1, :, 11]), k_new[1])
    np.testing.assert_array_equal(np.asarray(view["v"][1, :, 11]), v_new[1])
    # everything else is still zero
    assert float(jnp.abs(view["k"][0, :, 4:]).max()) == 0.0
    assert float(jnp.abs(view["k"][1, :, :11]).max()) == 0.0


@pytest.mark.parametrize("stacked", [False, True])
@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_window_write_rounds_match_one_scatter(layout, stacked):
    """A window wider than one scatter round (70 positions: three rounds,
    the last padded) lands exactly the live positions of each row —
    a mid-page start, a masked-out row, a row live to the last column —
    and nothing else, per layer or addressed in the stacked cache."""
    cfg = configs.get_config("granite-8b", reduced=True)
    b, w, hd = 3, 70, cfg.resolved_head_dim
    kw = dict(layout="paged", page_size=8, num_pages=49) if layout == "paged" else {}
    cache = kvc.init_attention_cache(cfg, b, 128, dtype=jnp.float32, **kw)
    if layout == "paged":  # slot i owns pages 1 + 16 i ..
        cache["page_table"] = 1 + jnp.arange(b * 16, dtype=jnp.int32).reshape(b, 16)
    rng = np.random.default_rng(1)
    base = {
        n: jnp.asarray(rng.normal(size=leaf.shape), jnp.float32)
        for n, leaf in cache.items() if n != "page_table"
    }
    cache.update(base)
    starts, lens = np.array([5, 0, 30]), np.array([50, 0, 70])
    t = np.arange(w)
    positions = np.where(t < lens[:, None], starts[:, None] + t, 128)
    upd = {
        n: jnp.asarray(rng.normal(size=(b, cfg.n_kv_heads, w, hd)), jnp.float32)
        for n in ("k", "v")
    }
    want = {n: np.array(v) for n, v in kvc.decode_view(cache, cfg.n_kv_heads).items()}
    for n in upd:
        for i in range(b):
            want[n][i][:, starts[i]:starts[i] + lens[i]] = np.asarray(upd[n][i])[:, :lens[i]]
    if stacked:  # layer 1 of two, addressed in place
        two = {n: jnp.stack([jnp.zeros_like(x), x]) for n, x in cache.items()}
        out = kvc.window_write(kvc.layer_cache(two, 1), upd, jnp.asarray(positions))
        assert all(float(jnp.abs(x[0]).max()) == 0 for n, x in kvc.stacked_cache(out).items() if n != "page_table")
    else:
        out = kvc.window_write(cache, upd, jnp.asarray(positions))
    got = kvc.decode_view(out, cfg.n_kv_heads)
    for n in upd:
        np.testing.assert_array_equal(np.asarray(got[n]), want[n])


# --------------------------------------------------------- sharding -------


def test_cache_shardings_compose_for_both_layouts():
    from repro.distributed.sharding import ShardingRules, cache_shardings
    from repro.launch.mesh import make_mesh

    cfg = configs.get_config("granite-8b", reduced=True)
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = ShardingRules(mesh)
    dense = cache_shardings(rules, cfg, batch=2, max_len=64)
    assert set(dense["layers"]) == {"k", "v"}
    paged = cache_shardings(
        rules, cfg, batch=2, max_len=64, layout="paged",
        page_size=16, num_pages=9,
    )
    assert set(paged["layers"]) == {"k", "v", "page_table"}
    # every leaf got a NamedSharding (composition holds for pool shapes)
    for leaf in jax.tree.leaves(paged):
        assert leaf is not None


def test_model_serve_policy_untouched_by_layout():
    """kv_layout is orthogonal to precision: the engine's resolved plan is
    identical across layouts."""
    cfg = configs.get_config("granite-8b", reduced=True)
    cfg = dataclasses.replace(cfg, precision="int8_serve")
    params = _params(cfg)
    eng_d = ServingEngine(cfg, params, _serve("dense"))
    eng_p = ServingEngine(cfg, params, _serve("paged"))
    assert eng_d.plan == eng_p.plan
    assert eng_d.quant_cache and eng_p.quant_cache
