"""PR-8 pipelined engine loop + mesh-sharded decode tests: the
token-identity matrix (async greedy streams bit-identical to the
synchronous loop across dense/paged x GQA/MLA/int8-KV, under forced
preemption, mid-flight cancel, and EDF deadline drops), virtual-clock
determinism one step late (seeded Poisson replay), the in-flight
dispatch protocol (``Slot.inflight`` marks + discard-at-collect on
preemption), the overlap tracer mode, the
data-parallel :class:`~repro.serve.router.ReplicaRouter`, and the jit
program budget with *everything* enabled at once (async + sharded +
overlap tracer + EDF + prefix cache + preemption + chunked prefill)."""

import dataclasses
import warnings

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding

from repro import configs
from repro.configs.base import ServeConfig
from repro.core import precision as P
from repro.models import lm
from repro.serve import (
    Engine,
    ReplicaRouter,
    SamplingParams,
    StepClock,
    workloads,
)
from repro.serve.phases import PHASES, OverlapTracer, make_tracer
from repro.serve.scheduler import Slot

KEY = jax.random.PRNGKey(17)

KV8 = P.PrecisionPolicy(
    "kv8", (P.Rule("kv_cache", P.int8(per_channel=False)),)
)


@pytest.fixture(scope="module")
def cfg():
    return configs.get_config("granite-8b", reduced=True)


@pytest.fixture(scope="module")
def params(cfg):
    return lm.init_params(cfg, KEY)


def _serve(**kw):
    base = dict(
        max_batch=2, max_seq_len=64, prefill_buckets=(8, 16, 32),
        decode_steps=3, temperature=0.0,
    )
    base.update(kw)
    return ServeConfig(**base)


PROMPTS = (
    [5, 9, 3, 7],
    [11, 2, 6],
    [1, 2, 3, 4, 5, 6, 7, 8, 9],
    [4, 4],
    [8, 1, 6, 2, 9],
)


def _generate_tokens(cfg, params, sc, prompts=PROMPTS, max_new=8, **ekw):
    eng = Engine(cfg, params, sc, **ekw)
    handles = [eng.submit(list(p), max_new_tokens=max_new) for p in prompts]
    fin = eng.generate()
    return [tuple(fin[h.uid].generated) for h in handles], eng


# ------------------------------------------------- token-identity matrix --


@pytest.mark.parametrize(
    "arch,policy",
    [
        ("granite-8b", None),   # GQA float (bit-exact datapath)
        ("minicpm3-4b", None),  # MLA float
        ("granite-8b", KV8),    # GQA int8 KV (per-page scales)
    ],
)
@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_async_greedy_identical_to_sync(arch, policy, layout):
    """The acceptance bar for the pipelined loop: greedy token streams
    bit-identical to the synchronous loop on every datapath x layout."""
    acfg = configs.get_config(arch, reduced=True)
    aparams = lm.init_params(acfg, KEY)
    kw = dict(kv_layout=layout, kv_page_size=8, policy=policy)
    sync, _ = _generate_tokens(acfg, aparams, _serve(**kw))
    pipe, eng = _generate_tokens(
        acfg, aparams, _serve(async_loop=True, **kw)
    )
    assert pipe == sync
    assert eng.executor.async_loop


def test_async_identical_under_forced_preemption(cfg, params):
    """A page pool too small for two residents forces preemption cycles;
    a victim with an uncollected dispatch has its in-flight tokens
    discarded at collect and regenerates them after resume, so the
    streams stay bit-identical to the synchronous loop."""
    kw = dict(
        max_seq_len=32, decode_steps=2, kv_layout="paged",
        kv_page_size=8, kv_pages=5, kv_prefix_cache=True,
        kv_preemption=True,
    )
    prompts = [[3 + i, 1, 4] for i in range(4)]
    sync, sref = _generate_tokens(
        cfg, params, _serve(**kw), prompts=prompts, max_new=20
    )
    pipe, eng = _generate_tokens(
        cfg, params, _serve(async_loop=True, **kw),
        prompts=prompts, max_new=20,
    )
    assert pipe == sync
    # preemption actually happened in both runs or this test is inert
    assert sref.telemetry["preemptions"] > 0
    assert eng.telemetry["preemptions"] > 0
    eng.executor.cache_mgr.check_invariants()


def test_async_runs_are_deterministic(cfg, params):
    """Same seed, same prompts -> the pipelined loop reproduces itself
    exactly (no host/device race can leak into token streams)."""
    sc = _serve(async_loop=True, kv_layout="paged", kv_page_size=8)
    a, _ = _generate_tokens(cfg, params, sc)
    b, _ = _generate_tokens(cfg, params, sc)
    assert a == b


# ------------------------------------------------------ stale boundaries --


def test_mid_flight_cancel_discards_inflight_tokens(cfg, params):
    """Cancelling while a dispatch is in flight: the cancelled stream
    stops (at most the uncollected step's tokens are discarded — never
    routed), its pages free, and the surviving request is unharmed."""
    eng = Engine(cfg, params, _serve(
        async_loop=True, kv_layout="paged", kv_page_size=8,
    ))
    ha = eng.submit(list(PROMPTS[0]), max_new_tokens=12)
    hb = eng.submit(list(PROMPTS[1]), max_new_tokens=12)
    for _ in range(3):  # prefill + a couple of pipelined decode steps
        eng.step()
    gen_at_cancel = len(eng.request(ha).generated)
    assert eng.cancel(ha)
    assert eng.finish_reason(ha) == "cancelled"
    fin = eng.generate()
    # the cancelled request never grew past the in-flight boundary
    assert len(eng.request(ha).generated) <= gen_at_cancel + 1
    assert hb.uid in fin and len(fin[hb.uid].generated) == 12
    # pool is clean: the cancelled slot's pages went back
    eng.executor.cache_mgr.check_invariants()
    assert not eng.has_work


def test_edf_drops_identical_and_deterministic(cfg, params):
    """EDF deadline drops act on queued requests only, so the one-step-
    stale boundary cannot corrupt them: the same seeded Poisson workload
    on a virtual clock completes/drops identically across two async runs
    and matches the synchronous loop's totals."""
    def run(async_loop):
        clock = StepClock()
        eng = Engine(
            cfg, params,
            _serve(async_loop=async_loop, scheduler="edf"),
            clock=clock,
        )
        events = workloads.poisson(
            rate=100.0, n=24, vocab_size=cfg.vocab_size, seed=3,
            prompt_len=(3, 10), max_new_tokens=6, deadline_s=(0.05, 0.6),
        )
        rep = workloads.replay(eng, events, step_cost=0.02)
        return rep

    def virtual(rep):
        d = rep.as_dict()
        d.pop("host_wall_s")  # real seconds, legitimately run-dependent
        return d

    sync = run(False)
    async_a, async_b = run(True), run(True)
    # async is deterministic with itself, bit for bit (virtual-clock
    # accounting only; host wall seconds are physical measurements)
    assert virtual(async_a) == virtual(async_b)
    assert async_a.per_request == async_b.per_request
    # and agrees with sync on what was served vs dropped
    assert async_a.requests == sync.requests
    assert async_a.completed == sync.completed
    assert async_a.dropped == sync.dropped
    assert async_a.tokens == sync.tokens


def test_token_events_stamped_with_dispatch_clock(cfg, params):
    """Satellite contract: TokenEvents carry the engine clock of the
    step that *dispatched* them, so on a virtual clock the async loop's
    event timeline is reproducible (collect-time stamping would shift
    every event one step_cost late and wobble TTFT accounting)."""
    def run():
        clock = StepClock()
        eng = Engine(cfg, params, _serve(async_loop=True), clock=clock)
        h = eng.submit(list(PROMPTS[0]), max_new_tokens=6)
        events = []
        it = eng.stream(h)
        while True:
            ev = next(it, None)
            if ev is None:
                break
            events.append((ev.token, ev.index, ev.ts))
            clock.advance(0.01)
        return events

    assert run() == run()


def test_inflight_marks_track_uncollected_dispatch(cfg, params):
    """The executor marks decode slots in flight at dispatch and clears
    them at collect — but only when no newer dispatch re-marked the slot
    (the async loop dispatches N+1 before collecting N over the same
    slots).  The marks tell policies which residents carry an
    uncollected dispatch; preempting one is legal (discard-at-collect)
    but discards up to decode_steps tokens."""
    assert Slot().inflight is False
    eng = Engine(cfg, params, _serve(async_loop=True))
    eng.submit(list(PROMPTS[0]), max_new_tokens=8)
    eng.step()  # prefill dispatch + first decode dispatch in flight
    eng.step()
    marked = [i for i, s in enumerate(eng.executor.slots) if s.inflight]
    # a decode dispatch is pending -> its slots are marked
    assert marked == list(eng._inflight.decode_set)
    eng.generate()
    assert not any(s.inflight for s in eng.executor.slots)


def test_preempted_inflight_tokens_are_discarded_at_collect(cfg, params):
    """The admit_seq snapshot guard: a slot whose resident turned over
    between dispatch and collect — even back to the SAME request, whose
    identity check alone would pass — must not have the stale dispatch's
    tokens routed (the resume replay was planned from pre-dispatch
    ``generated``; routing them would duplicate tokens)."""
    eng = Engine(cfg, params, _serve(async_loop=True))
    h = eng.submit(list(PROMPTS[0]), max_new_tokens=12)
    eng.step()
    eng.step()  # a decode dispatch for h is now in flight
    inflight = eng._inflight
    assert inflight is not None and inflight.decode_set
    idx = inflight.decode_set[0]
    req = eng.executor.slots[idx].request
    before = len(req.generated)
    # simulate a mid-flight preempt + same-slot re-admission: the slot
    # record turns over but holds the same Request with a new admit stamp
    slot = eng.executor.slots[idx]
    slot.admit_seq += 1
    out = eng.executor.collect(inflight)
    eng._inflight = None
    assert len(req.generated) == before  # in-flight tokens discarded
    assert not any(t[0] == req.uid for t in out.tokens)


# ---------------------------------------------------------- overlap mode --


def test_overlap_tracer_never_fences_and_reports_overlap(cfg, params):
    eng = Engine(cfg, params, _serve(
        async_loop=True, trace_phases=True, phase_mode="overlap",
    ))
    assert isinstance(eng._tracer, OverlapTracer)
    eng.generate([list(p) for p in PROMPTS[:3]], max_new_tokens=6)
    assert eng._tracer.fences == 0  # never blocks the pipeline
    s = eng.telemetry["phases"]
    for key in ("device_overlap_s", "host_bubble_s", "overlap_efficiency"):
        assert key in s
    assert s["device_overlap_s"] > 0.0
    assert 0.0 <= s["overlap_efficiency"] <= 1.0
    # per-step records stay within the extended schema
    for rec in eng._tracer.records():
        assert set(rec) <= set(PHASES) | {"wall", "collect", "overlap"}


def test_make_tracer_mode_dispatch():
    assert isinstance(make_tracer(True, mode="overlap"), OverlapTracer)
    assert make_tracer(True, mode="fenced").collect_phase == "sample"
    assert make_tracer(False, mode="overlap").collect_phase == "sample"
    with pytest.raises(ValueError, match="phase_mode"):
        make_tracer(True, mode="bogus")


def test_fenced_tracer_with_async_loop_warns(cfg, params):
    with pytest.warns(UserWarning, match="serializing the async_loop"):
        Engine(cfg, params, _serve(
            async_loop=True, trace_phases=True, phase_mode="fenced",
        ))
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # overlap mode must NOT warn
        Engine(cfg, params, _serve(
            async_loop=True, trace_phases=True, phase_mode="overlap",
        ))


# --------------------------------------------------- mesh-sharded decode --


def test_shard_decode_places_named_shardings(cfg, params):
    """shard_decode commits params and KV pools to NamedSharding over a
    tensor-parallel (data 1, model n) mesh of the devices the params are
    committed to; params on one device give a one-device mesh, which
    must be a semantic no-op (identical tokens) while every cache leaf
    is mesh-placed."""
    kw = dict(kv_layout="paged", kv_page_size=8)
    sync, _ = _generate_tokens(cfg, params, _serve(**kw))
    sharded, eng = _generate_tokens(
        cfg, params, _serve(shard_decode=True, async_loop=True, **kw)
    )
    assert sharded == sync
    assert eng.executor.mesh is not None
    assert dict(eng.executor.mesh.shape) == {"data": 1, "model": 1}
    assert set(eng.executor.mesh.devices.flat) == {jax.devices()[0]}
    assert eng.telemetry["replicated_param_leaves"] == 0
    for leaf in jax.tree.leaves(eng.executor.caches):
        assert isinstance(leaf.sharding, NamedSharding)
    for leaf in jax.tree.leaves(eng.executor.params):
        assert isinstance(leaf.sharding, NamedSharding)
    # the page-table rebuild hook keeps the committed placement
    assert eng.executor.cache_mgr.table_sharding is not None


def test_jit_budget_with_everything_enabled(cfg, params):
    """THE budget gate for this PR (CI-enforced): async loop + sharded
    decode + overlap tracer + EDF + prefix cache + preemption + chunked
    prefill + speculative decoding + mixed per-request sampling + n-best
    forking together still mint at most len(prefill_buckets) prefill
    programs + 1 decode + 1 extend on the target — no feature may re-key
    a jit cache mid-run (the page-table re-placement hook is what this
    catches).  The draft worker owns its own bounded set (at most
    len(prefill_buckets) draft prefills + 1 propose scan)."""
    clock = StepClock()
    eng = Engine(cfg, params, _serve(
        async_loop=True, shard_decode=True, trace_phases=True,
        phase_mode="overlap", scheduler="edf", kv_layout="paged",
        kv_page_size=8, kv_prefix_cache=True, kv_preemption=True,
        prefill_chunk=8, speculative=True, spec_tokens=3,
    ), clock=clock)
    events = workloads.poisson(
        rate=50.0, n=12, vocab_size=cfg.vocab_size, seed=0,
        max_new_tokens=6, deadline_s=(0.5, 5.0), shared_prefix=8,
    )
    workloads.replay(eng, events, step_cost=0.1)
    # mixed per-request sampling + an n-best fork on the same engine:
    # knobs ride the dispatches as stacked arrays, never as new programs
    eng.submit([5, 9, 3], SamplingParams(max_new_tokens=4))
    eng.submit([2, 4, 6, 8], SamplingParams(
        max_new_tokens=4, temperature=0.9, top_k=12, top_p=0.95, seed=7))
    eng.submit([7, 7, 1], SamplingParams(
        max_new_tokens=4, temperature=0.7, seed=11), n=2)
    eng.generate()

    def programs(fn):
        size = getattr(fn, "_cache_size", None)
        return size() if callable(size) else 1

    ex = eng.executor
    buckets = ex.buckets
    assert sum(programs(f) for f in ex._prefill_fn.values()) <= len(buckets)
    # a fully-speculative steady state can retire every token through the
    # verify dispatch without ever compiling the decode scan — hence <= 1
    assert programs(ex._decode_fn) <= 1
    if ex._extend_fn is not None:
        assert programs(ex._extend_fn) <= 1
    assert ex.draft is not None
    assert programs(ex.draft._propose_fn) <= 1
    assert sum(
        programs(f) for f in ex.draft._prefill_fn.values()
    ) <= len(buckets)
    assert eng.telemetry["draft_tokens_proposed"] > 0
    assert eng._tracer.fences == 0


# ---------------------------------------------------------- replica router --


def test_router_greedy_identical_to_single_engine(cfg, params):
    single = Engine(cfg, params, _serve())
    hs = [single.submit(list(p), max_new_tokens=6) for p in PROMPTS]
    fin = single.generate()
    want = [fin[h.uid].generated for h in hs]

    router = ReplicaRouter(cfg, params, _serve(replicas=2, async_loop=True))
    rhs = [router.submit(list(p), max_new_tokens=6) for p in PROMPTS]
    rfin = router.generate()
    got = [rfin[h.uid].generated for h in rhs]
    assert got == want


def test_router_least_loaded_admission_balances(cfg, params):
    router = ReplicaRouter(cfg, params, _serve(replicas=3))
    handles = [
        router.submit(list(PROMPTS[i % len(PROMPTS)]), max_new_tokens=4)
        for i in range(9)
    ]
    placed = [router.replica_of(h) for h in handles]
    counts = [placed.count(i) for i in range(3)]
    assert counts == [3, 3, 3]  # round-robin falls out of least-loaded
    router.generate()
    assert not router.has_work


def test_router_stream_and_cancel_delegate(cfg, params):
    router = ReplicaRouter(cfg, params, _serve(replicas=2))
    ha = router.submit(list(PROMPTS[0]), max_new_tokens=5)
    hb = router.submit(list(PROMPTS[1]), max_new_tokens=5)
    events = list(router.stream(ha))
    # events re-stamped with the ROUTER uid, gapless and ordered
    assert [e.uid for e in events] == [ha.uid] * len(events)
    assert [e.index for e in events] == list(range(len(events)))
    assert events[-1].finished
    assert router.cancel(hb) or router.result(hb) is not None
    router.generate()
    tel = router.telemetry
    assert tel["replicas"] == 2
    assert len(tel["replica_telemetry"]) == 2
    assert tel["tokens_generated"] >= len(events)


def test_router_rejects_bad_replicas(cfg, params):
    with pytest.raises(ValueError, match="replicas"):
        ReplicaRouter(cfg, params, _serve(replicas=0))


# -------------------------------------------------------- sync unchanged --


def test_sync_loop_is_untouched_by_default(cfg, params):
    """async_loop defaults off and the sync path never creates carry
    state or in-flight steps — the legacy loop is byte-identical."""
    eng = Engine(cfg, params, _serve())
    eng.generate([list(p) for p in PROMPTS[:2]], max_new_tokens=5)
    assert not eng.executor.async_loop
    assert eng.executor._carry is None
    assert eng._inflight is None
    assert not eng.executor._carry_valid.any()
