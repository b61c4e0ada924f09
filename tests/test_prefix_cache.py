"""Prefix-cache page sharing + page-aware preemption test layer.

Three tiers, mirroring how the subsystem can fail:

* **Property-based CacheManager traces** (hypothesis, with the
  deterministic shim fallback): random admit / ensure / register / free /
  CoW / preempt-style op sequences against the raw manager, asserting the
  pool invariants after every operation — refcount conservation, no page
  leaked or double-freed, trash page 0 never allocated or mapped,
  ``pages_in_use`` == distinct live table entries, index/page-key
  consistency.
* **Randomized scheduler stress**: random admission order, prompt
  lengths, generation budgets, and pool sizes — the paged engine with
  prefix caching AND preemption enabled must stay token-identical to the
  dense engine for every request, across GQA / MLA / int8-KV.
* **Targeted scenarios**: prefill-skip savings, copy-on-write on
  full-coverage hits, page retention after the first tenant finishes,
  LRU eviction under pressure, preemption-resume equality + telemetry,
  and the zero-capacity ``page_utilization`` guard.

The tiered-KV-cache (host victim tier) section runs all three shapes
against the two-tier manager: property traces with spill/swap/flush
ops, swap-back token identity across GQA / MLA / int8-KV, and the
warm-prefix tenant-cycling scenario where the tier must convert
spilled prefixes into prefill skips at identical token output.
"""

import dataclasses

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # pragma: no cover - minimal images use the shim
    from _hypothesis_shim import given, settings, st

from repro import configs
from repro.configs.base import ServeConfig
from repro.core import precision as P
from repro.models import lm
from repro.serve import CacheManager, ServingEngine
from repro.serve import kv_cache as kvc

KEY = jax.random.PRNGKey(11)

KV8 = P.PrecisionPolicy(
    "kv8", (P.Rule("kv_cache", P.int8(per_channel=False)),)
)

PAGE = 8  # page size used throughout; one full page = one shareable unit
PREAMBLE = [7, 1, 3, 9, 2, 8, 4, 6]  # exactly one page of shared prefix


def _params(cfg):
    return lm.init_params(cfg, KEY)


def _serve(layout, **kw):
    base = dict(max_batch=2, max_seq_len=64, kv_layout=layout,
                kv_page_size=PAGE, decode_steps=3)
    base.update(kw)
    return ServeConfig(**base)


def _generate(cfg, params, serve_cfg, prompts, n_new=6, seed=0):
    eng = ServingEngine(cfg, params, serve_cfg, seed=seed)
    uids = [eng.submit(list(p), n_new) for p in prompts]
    res = eng.run()
    return eng, [res[u].generated for u in uids]


# =========================================================================
# Tier 1: property-based CacheManager traces
# =========================================================================


def _trace_manager(pool_pages, page_size, seed, host_pages=0):
    """Drive one random op trace against a raw paged CacheManager with the
    prefix cache on, mimicking the engine's calling discipline (reserve
    check before admit, ensure-with-write-range before decode writes,
    free on finish/preempt), and assert the pool invariants after every
    single operation.  With ``host_pages`` > 0 the victim tier is live:
    evictions spill to the host ring, matches can resolve from either
    tier, and the flush op drains queued spill/swap-in copies against
    real device caches — the invariants then also audit the host tier
    (no chain key served by both tiers, no host slot leaked or
    double-booked)."""
    cfg = configs.get_config("granite-8b", reduced=True)
    max_seq = page_size * 8
    sc = ServeConfig(
        max_batch=4, max_seq_len=max_seq, kv_layout="paged",
        kv_page_size=page_size, kv_pages=pool_pages, kv_prefix_cache=True,
        kv_host_pages=host_pages,
    )
    mgr = CacheManager(cfg, sc)
    caches = mgr.init_device_caches() if host_pages else None
    rng = np.random.default_rng(seed)
    live: dict[int, dict] = {}  # slot -> {"tokens": [...], "pos": int}
    vocab = 5  # tiny vocab makes shared prefixes common
    for _ in range(40):
        op = rng.integers(0, 5)
        if op == 0 and len(live) < sc.max_batch:  # admit (maybe prefix hit)
            slot = next(i for i in range(sc.max_batch) if i not in live)
            n = int(rng.integers(1, max_seq // 2))
            if live and rng.integers(0, 2):  # borrow a resident's prefix
                donor = live[list(live)[0]]["tokens"]
                tokens = donor[: max(1, n // 2)] + list(
                    rng.integers(0, vocab, max(1, n // 2))
                )
            else:
                tokens = list(rng.integers(0, vocab, n))
            reserve = min(len(tokens) + int(rng.integers(1, 16)), max_seq)
            match = mgr.match_prefix(tokens)
            lazy = bool(match) and len(tokens) > 1 and rng.integers(0, 2)
            wf = (
                min(match.tokens, len(tokens) - 1)
                if lazy else len(tokens)
            )
            need = mgr.admission_need(match, reserve, wf)
            if mgr.can_reserve(need):
                mgr.admit(slot, tokens, reserve, match=match,
                          lazy_tail=lazy, write_from=wf)
                live[slot] = {"tokens": list(tokens), "pos": wf,
                              "reserve": reserve}
        elif op == 1 and live:  # decode growth (+ CoW when range overlaps)
            slot = int(rng.choice(list(live)))
            state = live[slot]
            upto = min(state["pos"] + int(rng.integers(1, 4)),
                       state["reserve"])
            if upto > state["pos"]:
                mgr.ensure(slot, upto, write_from=state["pos"])
                # decode "writes" random generated tokens
                grow = max(upto - len(state["tokens"]), 0)
                state["tokens"] += list(rng.integers(0, vocab, grow))
                state["pos"] = upto
        elif op == 2 and live:  # register decode-completed pages
            slot = int(rng.choice(list(live)))
            state = live[slot]
            mgr.register_filled(slot, state["tokens"], state["pos"])
        elif op == 3 and live:  # finish or preempt: both just free
            slot = int(rng.choice(list(live)))
            mgr.free(slot)
            del live[slot]
        else:  # flush pending device work, as a dispatch host_prep would
            if caches is not None:
                caches = mgr.flush_swaps(caches)
                caches = mgr.flush_copies(caches)
            else:  # no tier: device side is exercised by the engine
                # tests; here we only keep the queue bounded
                mgr._pending_copies.clear()
        mgr.check_invariants()
    for slot in list(live):
        mgr.free(slot)
    mgr.check_invariants()
    # every request finished: nothing live, nothing lost
    assert mgr.pages_in_use == 0
    st_ = mgr.stats()
    assert st_.pages_cached + len(mgr._free) == st_.pages_capacity


@settings(max_examples=20, deadline=None)
@given(
    st.integers(6, 24),   # pool pages (incl. trash)
    st.sampled_from([2, 4, 8]),  # page size
    st.integers(0, 10_000),      # trace seed
)
def test_manager_invariants_under_random_traces(pool, page_size, seed):
    _trace_manager(pool, page_size, seed)


@settings(max_examples=10, deadline=None)
@given(
    st.integers(6, 16),          # pool pages (incl. trash) — tight, so
    st.sampled_from([2, 4]),     # evictions (hence spills) are common
    st.integers(0, 10_000),      # trace seed
    st.sampled_from([2, 6, 12]),  # host victim-tier capacity
)
def test_manager_invariants_with_victim_tier(pool, page_size, seed, host):
    """The random-trace property, with the host victim tier live: every
    eviction spills, matches resolve from either tier, flushes move real
    rows, and the two-tier invariants hold after every operation."""
    _trace_manager(pool, page_size, seed, host_pages=host)


def test_victim_tier_fetches_a_key_back_before_its_spill_flushes():
    """A trace whose admission fetches back a chain key whose spill has
    not flushed yet, then evicts for the fetched page: the spill's slot is
    then in transit (its swap-in pending) rather than indexed, which the
    invariants allow because spills land before swap-ins."""
    _trace_manager(9, 2, 5244, host_pages=2)


def test_invariant_checker_catches_corruption():
    """The checker itself must fail loudly on a corrupted pool (otherwise
    the property test above proves nothing)."""
    cfg = configs.get_config("granite-8b", reduced=True)
    sc = ServeConfig(max_batch=2, max_seq_len=32, kv_layout="paged",
                     kv_page_size=8, kv_pages=8, kv_prefix_cache=True)
    mgr = CacheManager(cfg, sc)
    mgr.admit(0, [1, 2, 3], 10)
    mgr.check_invariants()
    page = mgr._slot_pages[0][0]
    mgr._free.append(page)  # double-book: live AND free
    with pytest.raises(AssertionError, match="free list"):
        mgr.check_invariants()


# =========================================================================
# Tier 2: randomized scheduler stress — paged+prefix+preemption == dense
# =========================================================================


def _stress_case(arch, policy, seed):
    cfg = configs.get_config(arch, reduced=True)
    params = _params(cfg)
    rng = np.random.default_rng(seed)
    preamble = list(rng.integers(0, cfg.vocab_size, PAGE))
    prompts, budgets = [], []
    for _ in range(5):
        kind = rng.integers(0, 3)
        if kind == 0:  # full preamble + payload (page-aligned hit)
            p = preamble + list(
                rng.integers(0, cfg.vocab_size, rng.integers(1, 8))
            )
        elif kind == 1:  # exact repeat (full-coverage hit -> CoW)
            p = list(preamble)
        else:  # unrelated prompt
            p = list(rng.integers(0, cfg.vocab_size, rng.integers(2, 12)))
        prompts.append(p)
        budgets.append(int(rng.integers(2, 10)))
    kv_pages = int(rng.integers(8, 17))  # oversubscribed pool -> preemption

    def run(layout, **kw):
        eng = ServingEngine(
            cfg, params,
            _serve(layout, max_seq_len=32, policy=policy, **kw),
            seed=0,
        )
        uids = [eng.submit(p, b) for p, b in zip(prompts, budgets)]
        res = eng.run()
        assert sorted(res) == sorted(uids), "a request was lost"
        return eng, [res[u].generated for u in uids]

    _, dense = run("dense")
    eng, paged = run("paged", kv_pages=kv_pages, kv_prefix_cache=True,
                     kv_preemption=True)
    assert paged == dense, (
        f"paged+prefix+preemption diverged from dense for {arch}/{policy}"
    )
    eng.cache_mgr.check_invariants()
    assert eng.cache_mgr.stats().pages_in_use == 0


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 10_000))
def test_scheduler_stress_gqa(seed):
    _stress_case("granite-8b", None, seed)


@settings(max_examples=2, deadline=None)
@given(st.integers(0, 10_000))
def test_scheduler_stress_mla(seed):
    _stress_case("minicpm3-4b", None, seed)


@settings(max_examples=2, deadline=None)
@given(st.integers(0, 10_000))
def test_scheduler_stress_int8_kv(seed):
    _stress_case("granite-8b", KV8, seed)


# =========================================================================
# Tier 3: targeted scenarios
# =========================================================================


def test_prefix_skip_saves_prefill_and_matches_dense():
    """Same-preamble admissions on the bit-exact datapath skip the prefill
    dispatch for the shared pages (tail rides the decode scan teacher-
    forced) and still reproduce the dense token streams exactly."""
    cfg = configs.get_config("granite-8b", reduced=True)
    params = _params(cfg)
    prompts = [PREAMBLE + [5, 5], PREAMBLE + [5, 5], PREAMBLE + [2, 4, 1],
               PREAMBLE[:4]]  # last one: no full page -> miss
    _, dense = _generate(cfg, params, _serve("dense"), prompts, n_new=8)
    eng, paged = _generate(
        cfg, params, _serve("paged", kv_prefix_cache=True), prompts, n_new=8
    )
    assert paged == dense
    assert eng._prefix_skip  # float GQA: the skip path is live
    st_ = eng.cache_mgr.stats()
    assert st_.prefix_hits == 2 and st_.prefix_queries == 4
    assert 0 < st_.prefix_hit_rate < 1
    # both hits covered the full 8-token preamble page without recompute
    assert eng.telemetry["prefill_tokens_saved"] == 2 * len(PREAMBLE)
    eng.cache_mgr.check_invariants()


def test_full_coverage_hit_triggers_copy_on_write():
    """An exact-repeat prompt maps every page shared; its first decode
    write lands inside the last shared page and must CoW a private copy —
    the original tenant's stream and the repeat's stream both stay
    identical to dense."""
    cfg = configs.get_config("granite-8b", reduced=True)
    params = _params(cfg)
    prompts = [list(PREAMBLE)] * 3
    _, dense = _generate(cfg, params, _serve("dense"), prompts, n_new=8)
    eng, paged = _generate(
        cfg, params, _serve("paged", kv_prefix_cache=True), prompts, n_new=8
    )
    assert paged == dense
    assert eng.cache_mgr.stats().cow_copies >= 1
    eng.cache_mgr.check_invariants()


def test_retained_pages_hit_after_owner_finishes():
    """The amortization that matters for repeated-prompt physics
    workloads: wave 2 must hit pages whose tenants finished in wave 1
    (refcount-0 retention), not just co-resident sharing."""
    cfg = configs.get_config("granite-8b", reduced=True)
    params = _params(cfg)
    eng = ServingEngine(cfg, params, _serve("paged", kv_prefix_cache=True))
    u1 = [eng.submit(PREAMBLE + [5, 5], 6)]
    r1 = eng.run()
    assert eng.cache_mgr.stats().pages_cached > 0  # retained, not wiped
    u2 = [eng.submit(PREAMBLE + [9, 9, 9], 6)]
    r2 = eng.run()
    st_ = eng.cache_mgr.stats()
    assert st_.prefix_hits >= 1 and eng.telemetry["prefill_tokens_saved"] > 0
    # parity for both waves against a dense engine run the same way
    eng_d = ServingEngine(cfg, params, _serve("dense"))
    ud1 = [eng_d.submit(PREAMBLE + [5, 5], 6)]
    rd1 = eng_d.run()
    ud2 = [eng_d.submit(PREAMBLE + [9, 9, 9], 6)]
    rd2 = eng_d.run()
    assert [r1[u].generated for u in u1] == [rd1[u].generated for u in ud1]
    assert [r2[u].generated for u in u2] == [rd2[u].generated for u in ud2]
    eng.cache_mgr.check_invariants()


def test_lru_eviction_under_pool_pressure():
    """Retained pages are evictable: a stream of distinct prompts through
    a small pool must recycle cached pages (evictions > 0) without ever
    corrupting later requests."""
    cfg = configs.get_config("granite-8b", reduced=True)
    params = _params(cfg)
    sc = _serve("paged", max_seq_len=32, kv_pages=5, kv_prefix_cache=True)
    eng = ServingEngine(cfg, params, sc)
    eng_d = ServingEngine(cfg, params, _serve("dense", max_seq_len=32))
    rng = np.random.default_rng(3)
    for _ in range(6):
        # distinct full-page prompts: each wave retains its prompt page,
        # so the 4-page pool must start evicting LRU retained pages
        prompt = list(rng.integers(0, cfg.vocab_size, 10))
        u = eng.submit(prompt, 6)
        ud = eng_d.submit(prompt, 6)
        res, res_d = eng.run(), eng_d.run()
        assert res[u].generated == res_d[ud].generated
        eng.cache_mgr.check_invariants()
    assert eng.cache_mgr.stats().page_evictions > 0
    assert eng.cache_mgr.stats().pages_in_use == 0


def test_preemption_resumes_token_identical_with_telemetry():
    """A pool that cannot hold two growing residents + preemption: the
    youngest is evicted and resumed, its final stream equals both the
    dense run and the FIFO (never-preempted) paged run, and the
    preemption is recorded on the request and in engine telemetry."""
    cfg = configs.get_config("granite-8b", reduced=True)
    params = _params(cfg)
    prompts = ([7, 8, 9], [1, 2, 3])
    kw = dict(max_seq_len=32, kv_pages=5)  # 4 usable pages; each wants 3
    _, dense = _generate(cfg, params, _serve("dense", max_seq_len=32),
                         prompts, n_new=20)
    fifo_eng, fifo = _generate(cfg, params, _serve("paged", **kw),
                               prompts, n_new=20)
    pre_eng, pre = _generate(
        cfg, params, _serve("paged", kv_preemption=True, **kw),
        prompts, n_new=20,
    )
    assert dense == fifo == pre
    assert fifo_eng.telemetry["preemptions"] == 0
    assert pre_eng.telemetry["preemptions"] >= 1
    preempted = [r for r in pre_eng._finished.values() if r.preemptions]
    assert preempted, "no request recorded its preemption"
    # a re-admission must not double-count the prompt
    assert pre_eng.telemetry["prompts_admitted"] == len(prompts)
    assert all(len(g) == 20 for g in pre)
    pre_eng.cache_mgr.check_invariants()


def test_preemption_live_on_non_bit_exact_datapaths():
    """MLA / int8-KV decode datapaths are not bitwise the prefill
    datapath, so preempt-resume used to be silently gated off.  The
    cache-extend program replays the prompt with prefill math and the
    generated tail with decode math, so these engines now preempt for
    real — and every resumed stream stays dense-exact."""
    for arch, policy in (("minicpm3-4b", None), ("granite-8b", KV8)):
        cfg = configs.get_config(arch, reduced=True)
        params = _params(cfg)
        prompts = ([7, 8, 9], [1, 2, 3])
        kw = dict(max_seq_len=32, kv_pages=5, policy=policy)
        eng, paged = _generate(
            cfg, params, _serve("paged", kv_preemption=True, **kw),
            prompts, n_new=20,
        )
        assert eng._preempt_enabled, f"{arch}/{policy}: preemption gated off"
        assert eng.telemetry["preemptions"] >= 1
        _, dense = _generate(
            cfg, params, _serve("dense", max_seq_len=32, policy=policy),
            prompts, n_new=20,
        )
        assert paged == dense


def test_prefix_cache_inert_for_dense_layout():
    cfg = configs.get_config("granite-8b", reduced=True)
    params = _params(cfg)
    prompts = [PREAMBLE + [5], PREAMBLE + [5]]
    eng, out = _generate(
        cfg, params,
        _serve("dense", kv_prefix_cache=True, kv_preemption=True), prompts,
    )
    assert not eng.cache_mgr.prefix_cache and not eng._preempt_enabled
    st_ = eng.cache_mgr.stats()
    assert st_.prefix_queries == 0 and st_.prefix_hits == 0
    _, ref = _generate(cfg, params, _serve("dense"), prompts)
    assert out == ref


# =========================================================================
# Victim tier (tiered KV cache): spill on eviction, swap-back on hit
# =========================================================================

_TIER_KW = dict(kv_pages=13, kv_prefix_cache=True, kv_preemption=True)


def _tenant_waves(cfg, params, serve_cfg, *, seed=5, waves=6, n_new=6):
    """Cycle four tenants' 3-page preambles through an engine in waves
    of two.  With ``kv_pages=13`` (12 usable) two residents fill the
    pool, so each wave evicts the previous tenants' preamble pages and
    the next visit must either swap them back (tier on) or recompute
    (tier off).  Returns (engine, per-request generated streams)."""
    rng = np.random.default_rng(seed)
    preambles = [
        list(rng.integers(0, cfg.vocab_size, 3 * PAGE)) for _ in range(4)
    ]
    eng = ServingEngine(cfg, params, serve_cfg, seed=0)
    outs = []
    for wave in range(waves):
        uids = []
        for j in range(2):
            tenant = (wave * 2 + j) % 4
            prompt = preambles[tenant] + list(
                rng.integers(0, cfg.vocab_size, 4)
            )
            uids.append(eng.submit(prompt, n_new))
        res = eng.run()
        outs.extend(res[u].generated for u in uids)
        if serve_cfg.kv_layout == "paged":
            eng.cache_mgr.check_invariants()
    return eng, outs


def test_victim_tier_swap_back_restores_prefix_hits():
    """The tier's reason to exist: on a device pool below the warm
    working set, tier-off loses every tenant prefix between visits
    (zero savings) while tier-on swaps them back — majority of spills
    return (swap_hit_rate > 0.5), strictly more prefill tokens saved,
    and the greedy streams stay bit-identical."""
    cfg = configs.get_config("granite-8b", reduced=True)
    params = _params(cfg)
    off, off_out = _tenant_waves(cfg, params, _serve("paged", **_TIER_KW))
    on, on_out = _tenant_waves(
        cfg, params, _serve("paged", kv_host_pages=32, **_TIER_KW)
    )
    assert on_out == off_out  # identical token output, tier on or off
    t_on, t_off = on.telemetry, off.telemetry
    assert t_off["swap_outs"] == 0 and t_off["swap_ins"] == 0
    assert t_on["swap_outs"] > 0 and t_on["swap_ins"] > 0
    assert t_on["swap_ins"] / t_on["swap_outs"] > 0.5
    assert (
        t_on["prefill_tokens_saved"] > t_off["prefill_tokens_saved"]
    ), "the tier failed to convert spilled prefixes into prefill skips"
    assert t_on["host_pages_used"] > 0
    assert t_on["swap_latency_s"] >= 0.0
    on.cache_mgr.check_invariants()


def test_victim_tier_token_identity_across_datapaths():
    """Swap-back must restore byte-identical cache rows on every
    datapath the cache serves: GQA float, MLA latent pools, and the
    int8-KV pools with their per-page scales — each engine's streams
    must equal the dense reference."""
    for arch, policy in (
        ("granite-8b", None), ("minicpm3-4b", None), ("granite-8b", KV8)
    ):
        cfg = configs.get_config(arch, reduced=True)
        params = _params(cfg)
        eng, paged = _tenant_waves(
            cfg, params,
            _serve("paged", policy=policy, kv_host_pages=32, **_TIER_KW),
            waves=4,
        )
        _, dense = _tenant_waves(
            cfg, params, _serve("dense", policy=policy), waves=4
        )
        assert paged == dense, f"swap-back diverged for {arch}/{policy}"
        assert eng.telemetry["swap_ins"] > 0, (
            f"tier never exercised for {arch}/{policy}"
        )
        eng.cache_mgr.check_invariants()


def test_jit_budget_with_victim_tier():
    """All tier movement is host bookkeeping plus eager device copies
    outside jit: with spills and swap-backs live, the program set must
    stay at len(prefill_buckets) prefill + 1 decode."""
    cfg = configs.get_config("granite-8b", reduced=True)
    params = _params(cfg)
    sc = _serve("paged", prefill_buckets=(8, 16, 32), kv_host_pages=32,
                **_TIER_KW)
    eng, _ = _tenant_waves(cfg, params, sc)
    assert eng.telemetry["swap_ins"] > 0  # the tier was live
    assert eng.telemetry["prefill_compiles"] <= 3
    assert eng.telemetry["decode_compiles"] == 1


def test_invariant_checker_catches_two_tier_booking():
    """The two-tier checker must fail loudly on a chain key served by
    both tiers at once (otherwise the tier property test proves
    nothing about the corruption swap-back exists to prevent)."""
    cfg = configs.get_config("granite-8b", reduced=True)
    sc = ServeConfig(max_batch=2, max_seq_len=32, kv_layout="paged",
                     kv_page_size=8, kv_pages=8, kv_prefix_cache=True,
                     kv_host_pages=4)
    mgr = CacheManager(cfg, sc)
    mgr.admit(0, list(range(8)), 16)
    mgr.register_filled(0, list(range(8)), 8)
    page = mgr._slot_pages[0][0]
    key = mgr._page_key[page]
    mgr.check_invariants()
    host = mgr._host_free.pop()  # double-book the key onto the host ring
    mgr._host_index[key] = host
    mgr._host_key[host] = key
    with pytest.raises(AssertionError, match="both tiers"):
        mgr.check_invariants()


# =========================================================================
# Regression guards
# =========================================================================


def test_free_purges_pending_cow_copies():
    """Regression (satellite fix): a tenant finishing between its CoW
    ensure and the next dispatch used to leave the queued copy aimed at
    a freed page — the next tenant to reuse that page got stale rows
    scattered over its freshly prefilled content, because the prefill
    dispatch flushes pending copies after its own writes.  free() must
    purge pending copies whose destination returns to the free list."""
    cfg = configs.get_config("granite-8b", reduced=True)
    sc = ServeConfig(max_batch=2, max_seq_len=32, kv_layout="paged",
                     kv_page_size=8, kv_pages=8, kv_prefix_cache=True)
    mgr = CacheManager(cfg, sc)
    first = list(range(8))
    mgr.admit(0, first, 16)
    mgr.register_filled(0, first, 8)
    match = mgr.match_prefix(first)
    assert match.tokens == 8  # full-coverage hit: write lands in-page
    mgr.admit(1, first, 16, match=match, lazy_tail=True, write_from=7)
    mgr.ensure(1, 9, write_from=7)  # write inside the shared page -> CoW
    assert mgr._pending_copies, "scenario failed to queue a CoW copy"
    mgr.free(1)  # finish before any dispatch flushed the copy
    freed = set(mgr._free)
    assert not any(dst in freed for _, dst in mgr._pending_copies), (
        "pending CoW copy still targets a freed page"
    )
    mgr.check_invariants()


def test_admission_counts_revived_cached_pages():
    """Regression: reviving cached matched pages removes them from the
    evictable pool, so the admission check must charge for them — the old
    accounting over-promised the pool and crashed mid-decode with 'pool
    exhausted' despite the reservation discipline."""
    cfg = configs.get_config("granite-8b", reduced=True)
    sc = ServeConfig(max_batch=3, max_seq_len=40, kv_layout="paged",
                     kv_page_size=8, kv_pages=6, kv_prefix_cache=True)
    mgr = CacheManager(cfg, sc)
    first = list(range(16))
    mgr.admit(0, first, 16)
    mgr.free(0)  # both pages retained on the cached LRU
    mgr.admit(1, [1, 2, 3, 4, 5, 6, 7, 8], 24)  # 1 page live, 3 reserved
    match = mgr.match_prefix(first)
    assert len(match.pages) == 2
    # full-coverage hit: tail needs 1 + CoW headroom 1, plus 2 revivals;
    # the pool (2 free + 2 cached - 2 promised) cannot cover that
    need = mgr.admission_need(match, 24, 15)
    assert need == 4
    assert not mgr.can_reserve(need)
    with pytest.raises(RuntimeError, match="cannot reserve"):
        mgr.admit(2, first, 24, match=match, lazy_tail=True, write_from=15)
    mgr.check_invariants()
    # once the resident's reservation is gone, the same admission fits
    # and both residents can grow to their full reservations
    mgr.free(1)
    match = mgr.match_prefix(first)
    mgr.admit(2, first, 24, match=match, lazy_tail=True, write_from=15)
    mgr.ensure(2, 24, write_from=15)
    mgr.check_invariants()


def test_prefix_plus_preemption_tight_pool_terminates():
    """Regression (livelock): with the prefix cache AND preemption on a
    pool that holds only one resident, a skip-resumed victim used to
    spend its whole residency teacher-forcing its replay tail — emitting
    nothing — and was preempted again every step, forever.  A slot must
    emit at least one token per residency before it is preemptable, so
    every preemption cycle nets progress and the run terminates."""
    cfg = configs.get_config("granite-8b", reduced=True)
    params = _params(cfg)
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, cfg.vocab_size, rng.integers(4, 16)))
               for _ in range(4)]
    kw = dict(max_seq_len=32, kv_pages=5, decode_steps=4)
    # _generate raises KeyError if any request never finishes (livelock)
    eng, paged = _generate(
        cfg, params,
        _serve("paged", kv_prefix_cache=True, kv_preemption=True, **kw),
        prompts, n_new=20,
    )
    assert eng.telemetry["preemptions"] >= 1
    _, dense = _generate(
        cfg, params, _serve("dense", max_seq_len=32), prompts, n_new=20
    )
    # every request ran to its budget or the sequence cap — exactly as
    # far as the dense engine took it — and emitted identical tokens
    assert paged == dense
    assert all(g for g in paged)
    eng.cache_mgr.check_invariants()


def test_chain_key_intern_table_is_garbage_collected():
    """Regression (host-memory leak): every full page ever served interns
    a chain key; without the mark-sweep the table grows monotonically on
    a long-running engine.  After churning many distinct prompts through
    a small pool, the table must stay bounded by the reachable set — and
    retained prefixes must still match afterwards (fresh ids, no reuse)."""
    cfg = configs.get_config("granite-8b", reduced=True)
    sc = ServeConfig(max_batch=2, max_seq_len=32, kv_layout="paged",
                     kv_page_size=4, kv_pages=5, kv_prefix_cache=True)
    mgr = CacheManager(cfg, sc)
    mgr._intern_gc_floor = mgr._intern_gc_at = 8  # frequent sweeps at test scale
    keep = list(range(100, 108))  # 2 full pages we want to keep hitting
    mgr.admit(0, keep, 12)
    mgr.free(0)  # retained on the cached LRU
    for i in range(40):  # 40 distinct 1-page prompts churn the pool
        tokens = [200 + i] * 4
        match = mgr.match_prefix(tokens)
        if not mgr.can_reserve(mgr.admission_need(match, 8, len(tokens))):
            break
        mgr.admit(1, tokens, 8, match=match)
        mgr.free(1)
        mgr.check_invariants()
    assert len(mgr._key_intern) <= max(
        16, 4 * (len(mgr._prefix_index) + 1)
    ), "intern table grew without bound"
    # ids were never reused: the retained prefix still matches exactly
    match = mgr.match_prefix(keep + [1, 2])
    kept_pages = [p for p in mgr._cached if mgr._page_key.get(p)]
    if kept_pages:  # unless churn evicted it (pool pressure)
        assert match.tokens in (0, 8)


def test_preemption_never_outgrows_prefill_buckets():
    """Regression: a preempted request resumes with prompt + generated as
    its new prompt; if that outgrows the largest configured bucket the
    re-prefill would mint an exact-length jit program.  Such slots must
    not be preempted (FIFO fallback) so the program budget holds."""
    cfg = configs.get_config("granite-8b", reduced=True)
    params = _params(cfg)
    prompts = ([7, 8, 9], [1, 2, 3])
    kw = dict(max_seq_len=32, kv_pages=5, prefill_buckets=(4, 8),
              decode_steps=2)
    eng, paged = _generate(
        cfg, params, _serve("paged", kv_preemption=True, **kw),
        prompts, n_new=20,
    )
    assert all(len(g) == 20 for g in paged)
    # early preemptions (short resumes) happen; oversized resumes don't
    assert eng.telemetry["preemptions"] >= 1
    assert all(
        len(s.request.resume_tokens) <= 8
        for s in eng.slots if s.active
    )
    assert eng.telemetry["prefill_compiles"] <= 2
    assert eng.telemetry["decode_compiles"] == 1
    _, dense = _generate(
        cfg, params,
        _serve("dense", max_seq_len=32, prefill_buckets=(4, 8),
               decode_steps=2),
        prompts, n_new=20,
    )
    assert paged == dense


def test_page_utilization_guards_zero_capacity():
    """Regression (satellite): a zero-capacity stats row (max_batch=0
    dense probe, or a hand-rolled row) must report 0.0 utilization, not
    divide by zero."""
    row = kvc.CacheStats(
        layout="dense", kv_bytes=0, page_size=0, pages_in_use=0,
        pages_capacity=0, page_allocs_total=0, pages_in_use_peak=0,
    )
    assert row.page_utilization == 0.0
    assert row.prefix_hit_rate == 0.0
    assert row.as_dict()["page_utilization"] == 0.0
    cfg = configs.get_config("granite-8b", reduced=True)
    mgr = CacheManager(cfg, ServeConfig(max_batch=0, max_seq_len=32))
    assert mgr.stats().page_utilization == 0.0

    from benchmarks.serving_throughput import _page_util_peak

    assert _page_util_peak({}) == 0.0
    assert _page_util_peak({"pages_capacity": 0, "pages_in_use_peak": 3}) == 0.0
    assert _page_util_peak({"pages_capacity": 4, "pages_in_use_peak": 2}) == 0.5


def test_prefix_benchmark_reports_savings():
    """The serving benchmark's prefix-heavy mode must show a real hit
    rate and nonzero prefill-token savings (acceptance criterion)."""
    from benchmarks import serving_throughput as bench

    cfg = bench.physics_scale_lm()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    row = bench._sweep_one(
        "physics_scale", cfg, params, max_batch=2, buckets=(8, 16, 32),
        decode_steps=4, kv_layout="paged", workload="prefix", n_requests=4,
    )
    derived = row.rsplit(",", 1)[1]
    fields = dict(f.split("=") for f in derived.split(";"))
    assert float(fields["prefix_hit_rate"]) > 0
    assert int(fields["prefill_tokens_saved"]) > 0
    assert "preemptions" in fields


def test_jit_budget_with_prefix_and_preemption():
    """Sharing and preemption are host-side block-table operations: the
    jit cache must stay at len(prefill_buckets) prefill + 1 decode
    programs with both knobs on (CI enforces this alongside the per-
    layout budget test)."""
    cfg = configs.get_config("granite-8b", reduced=True)
    params = _params(cfg)
    rng = np.random.default_rng(0)
    prompts = [PREAMBLE + list(rng.integers(0, cfg.vocab_size, n))
               for n in (1, 2, 3, 5, 7)]
    prompts += [list(rng.integers(0, cfg.vocab_size, n)) for n in (3, 9, 13)]
    sc = _serve("paged", max_batch=4, prefill_buckets=(4, 8, 16),
                kv_prefix_cache=True, kv_preemption=True)
    eng, _ = _generate(cfg, params, sc, prompts)
    assert eng.cache_mgr.stats().prefix_hits > 0  # the knobs were live
    assert eng.telemetry["prefill_compiles"] <= len(eng.prefill_buckets)
    assert eng.telemetry["decode_compiles"] == 1

    def programs(fn):
        size = getattr(fn, "_cache_size", None)
        return size() if callable(size) else 1

    assert sum(programs(f) for f in eng._prefill_fn.values()) <= len(
        eng.prefill_buckets
    )
    assert programs(eng._decode_fn) == 1
