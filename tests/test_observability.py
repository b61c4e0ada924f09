"""Latency-observability tests: the per-step phase tracer (off costs
nothing — zero fences, no phase state; on — phases sum within step
wall time and land in Engine.telemetry), TokenEvent stream integrity
(monotone timestamps, gapless indices), merged-telemetry key-collision
protection, and the two-key queue-wait attribution for preempted
requests (restamped vs created_at-anchored)."""

import time

import jax
import numpy as np
import pytest

from repro import configs
from repro.configs.base import ServeConfig
from repro.models import lm
from repro.serve import Engine, StepClock
from repro.serve.phases import (
    NULL_TRACER,
    PHASES,
    NullTracer,
    PhaseTracer,
    _percentile,
    make_tracer,
)

KEY = jax.random.PRNGKey(17)


@pytest.fixture(scope="module")
def cfg():
    return configs.get_config("granite-8b", reduced=True)


@pytest.fixture(scope="module")
def params(cfg):
    return lm.init_params(cfg, KEY)


def _engine(cfg, params, **kw):
    base = dict(
        max_batch=2, max_seq_len=64, prefill_buckets=(8, 16, 32),
        decode_steps=2, temperature=0.0,
    )
    clock = kw.pop("clock", None)
    base.update(kw)
    return Engine(cfg, params, ServeConfig(**base), clock=clock)


PROMPTS = ([5, 9, 3, 7], [11, 2, 6], [1, 2, 3, 4, 5, 6, 7, 8], [4, 4])


# ------------------------------------------------------ tracer unit --


def _host_spans(logdir) -> list[tuple[str, dict]]:
    """(name, arguments) of every ``serve.*`` event on the host planes of
    the profiler trace written under ``logdir``."""
    from pathlib import Path

    from jax.profiler import ProfileData

    (path,) = Path(logdir).rglob("*.xplane.pb")
    data = ProfileData.from_file(str(path))
    return [(e.name, dict(e.stats)) for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("serve.")]


def test_null_tracer_is_inert(tmp_path):
    tr = make_tracer(False)
    assert tr is NULL_TRACER
    assert isinstance(tr, NullTracer)
    assert not tr.enabled
    # the fence is a pass-through that never touches the device, and
    # nothing counts fences
    sentinel = object()
    assert tr.fence(sentinel) is sentinel
    assert not hasattr(tr, "fences")
    # phases outside a profiler session: no records, no spans kept
    with tr.step():
        with tr.phase("sample", "decode"):
            pass
    tr.begin_step(), tr.end_step()
    assert tr.records() == []
    assert tr.summary() == {}
    # the same phases inside a session are spans; "device" waits for
    # nothing without a fence and writes none
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.step():
            with tr.phase("host_prep", "decode"):
                pass
            with tr.phase("device", "decode"):
                pass
    finally:
        jax.profiler.stop_trace()
    assert sorted(_host_spans(tmp_path)) == [
        ("serve.host_prep", {"program": "decode"}), ("serve.step", {})]
    assert tr.records() == []


def test_phase_tracer_accumulates_and_bounds_ring():
    tr = PhaseTracer(ring=3)
    for step in range(5):
        tr.begin_step()
        with tr.phase("schedule"):
            pass
        # re-entrant: two dispatch phases in one step sum
        with tr.phase("dispatch"):
            time.sleep(0.001)
        with tr.phase("dispatch"):
            time.sleep(0.001)
        tr.end_step()
    recs = tr.records()
    assert len(recs) == 3  # ring bounded
    for rec in recs:
        assert rec["dispatch"] >= 0.002
        assert rec["wall"] >= rec["dispatch"]
    s = tr.summary()
    assert s["steps"] == 3 and s["ring"] == 3
    assert s["dispatch"]["n"] == 3
    assert s["dispatch"]["p50_ms"] >= 2.0
    assert s["unattributed_s"] >= 0.0
    # phases absent from every step don't appear in the summary
    assert "device" not in s


def test_phase_tracer_validates_ring():
    with pytest.raises(ValueError, match="ring"):
        PhaseTracer(ring=0)


def test_percentile_nearest_rank():
    xs = [1.0, 2.0, 3.0, 4.0]
    assert _percentile([], 50) == 0.0
    assert _percentile(xs, 0) == 1.0
    assert _percentile(xs, 100) == 4.0
    assert _percentile(xs, 50) == 3.0  # nearest rank rounds up here
    assert _percentile([7.0], 99) == 7.0


# -------------------------------------------------- off costs nothing --


def test_tracer_off_by_default_no_fences_no_phases(cfg, params):
    """An untraced engine runs the shared NULL_TRACER: no fences, no
    phase records, empty 'phases' telemetry — the hot loop is the
    pre-tracer code path."""
    eng = _engine(cfg, params)
    assert eng.executor.tracer is NULL_TRACER
    for p in PROMPTS:
        eng.submit(list(p), max_new_tokens=6)
    eng.generate()
    assert eng.telemetry["phases"] == {}
    assert not hasattr(NULL_TRACER, "fences")  # nothing even counts


def test_tracer_off_throughput_guard(cfg, params):
    """The off path must not tax throughput: an untraced run of the same
    workload is not meaningfully slower than a traced (fenced) one.
    The traced run pays for fencing, so the generous bound only trips
    if the off path somehow grew overhead."""

    def wall(trace):
        eng = _engine(cfg, params, trace_phases=trace)
        for p in PROMPTS:
            eng.submit(list(p), max_new_tokens=8)
        eng.generate()  # warmup: compiles
        for p in PROMPTS:
            eng.submit(list(p), max_new_tokens=8)
        t0 = time.perf_counter()
        eng.generate()
        return time.perf_counter() - t0

    wall_on = wall(True)
    wall_off = wall(False)
    assert wall_off <= wall_on * 1.5 + 0.1


# ----------------------------------------------------- traced engine --


def test_traced_engine_phases_sum_within_wall(cfg, params):
    eng = _engine(cfg, params, trace_phases=True, phase_ring=64)
    for p in PROMPTS:
        eng.submit(list(p), max_new_tokens=6)
    eng.generate()
    tr = eng._tracer
    assert tr.enabled and tr.fences > 0
    recs = tr.records()
    assert recs
    for rec in recs:
        attributed = sum(v for k, v in rec.items() if k != "wall")
        # phases are disjoint spans inside the step: their sum can never
        # exceed the step's wall time (small epsilon for timer jitter)
        assert attributed <= rec["wall"] + 1e-4
        assert set(rec) - {"wall"} <= set(PHASES)
        assert "schedule" in rec
    ph = eng.telemetry["phases"]
    assert ph["steps"] == len(recs)
    for name in ("schedule", "device", "wall"):
        assert ph[name]["n"] > 0
        assert ph[name]["p50_ms"] <= ph[name]["p95_ms"] <= ph[name]["p99_ms"]
    # decode steps ran, so every phase of the model appeared somewhere
    assert {"host_prep", "dispatch", "device", "sample"} <= set(ph)


def test_phase_ring_knob_respected(cfg, params):
    eng = _engine(cfg, params, trace_phases=True, phase_ring=2)
    for p in PROMPTS:
        eng.submit(list(p), max_new_tokens=8)
    eng.generate()
    assert len(eng._tracer.records()) == 2
    assert eng.telemetry["phases"]["ring"] == 2


# ------------------------------------------------- stream integrity --


def test_token_events_monotone_and_gapless(cfg, params):
    """Per stream: timestamps never go backwards and indices count
    0,1,2,... with the final event flagged exactly once."""
    eng = _engine(cfg, params)
    handles = [eng.submit(list(p), max_new_tokens=7) for p in PROMPTS]
    for h in handles:
        events = list(eng.stream(h))
        assert events, "every request generates at least one token here"
        assert [ev.index for ev in events] == list(range(len(events)))
        assert all(a.ts <= b.ts for a, b in zip(events, events[1:]))
        assert [ev.finished for ev in events].count(True) == 1
        assert events[-1].finished
        assert events[-1].finish_reason == "length"
        assert all(ev.uid == h.uid for ev in events)


def test_token_event_ts_uses_engine_clock(cfg, params):
    clock = StepClock(t0=100.0)
    eng = _engine(cfg, params, clock=clock)
    h = eng.submit([1, 2, 3], max_new_tokens=4)
    events = list(eng.stream(h))
    assert all(ev.ts == 100.0 for ev in events)  # clock never advanced
    assert eng.result(h).finished_at == 100.0


# ------------------------------------------- telemetry key integrity --


@pytest.mark.parametrize("kv_host_pages", [0, 16])
def test_merged_telemetry_has_no_key_collisions(cfg, params, kv_host_pages):
    """Engine.telemetry merges four dicts + the SLO counters + the
    phases view; a key collision would silently shadow one layer's
    counter with another's.  Runs tier-off and tier-on: the victim
    tier's swap_outs/swap_ins/host_* keys live in the cache.stats
    layer and must stay disjoint from every other layer."""
    eng = _engine(cfg, params, kv_layout="paged", kv_prefix_cache=True,
                  kv_preemption=True, kv_host_pages=kv_host_pages)
    for p in PROMPTS:
        eng.submit(list(p), max_new_tokens=6)
    eng.generate()
    layers = {
        "executor.tel": set(eng.executor.tel),
        "scheduler.stats": set(eng.scheduler.stats),
        "cache.stats": set(eng.executor.cache_mgr.stats().as_dict()),
        "run_tel": set(eng._run_tel),
        "slo": set(eng._slo),
        "reserved": {"phases"},
    }
    names = sorted(layers)
    for i, a in enumerate(names):
        for b in names[i + 1:]:
            overlap = layers[a] & layers[b]
            assert not overlap, f"{a} and {b} both export {sorted(overlap)}"
    merged = eng.telemetry
    for keys in layers.values():
        assert keys <= set(merged)
    # the tier counters ride the cache.stats layer whether the tier is
    # configured or not (off: all-zero), so dashboards can always key on
    # them
    assert {"swap_outs", "swap_ins", "host_evictions", "host_pages_used",
            "host_pages_capacity", "swap_latency_s"} <= set(merged)
    assert merged["host_pages_capacity"] == kv_host_pages


# --------------------------------------------------- wait attribution --


def test_preemption_wait_attribution_two_keys(cfg, params):
    """Satellite: preempted requests report both waits — the restamped
    (submitted_at) wait that measures time-to-resume, and the
    created_at-anchored wait that keeps the full time-in-system
    (strictly larger once a preemption restamped the clock)."""
    clock = StepClock()
    eng = _engine(
        cfg, params, clock=clock, kv_layout="paged", kv_page_size=8,
        kv_pages=5, max_seq_len=32, kv_prefix_cache=True,
        kv_preemption=True,
    )
    rng = np.random.default_rng(0)
    handles = [
        eng.submit(list(rng.integers(0, cfg.vocab_size, 6)),
                   max_new_tokens=20)
        for _ in range(4)
    ]
    steps = 0
    while eng.has_work and steps < 400:
        eng.step()
        clock.advance(0.01)  # deterministic nonzero waits
        steps += 1
    assert not eng.has_work
    tel = eng.telemetry
    assert tel["preemptions"] > 0  # the pool genuinely thrashed
    preempted = [eng.result(h) for h in handles
                 if eng.result(h).preemptions > 0]
    assert preempted
    # restamping happened: the resumed admission's submitted_at moved
    # past the original created_at
    assert all(r.submitted_at > r.created_at for r in preempted)
    # the created-anchored total includes prior residencies, so it
    # strictly exceeds the restamped total once anything was preempted
    assert (tel["queue_wait_created_s_total"]
            > tel["queue_wait_s_total"])
    assert tel["queue_wait_created_s_total"] >= 0.0
    eng.generate()  # idle drain: stamps the run-level means
    assert (eng._run_tel["queue_wait_created_s_mean"]
            >= eng._run_tel["queue_wait_s_mean"])


def test_wait_keys_equal_without_preemption(cfg, params):
    clock = StepClock()
    eng = _engine(cfg, params, clock=clock)
    for p in PROMPTS:
        eng.submit(list(p), max_new_tokens=4)
    while eng.has_work:
        eng.step()
        clock.advance(0.01)
    tel = eng.telemetry
    assert tel["preemptions"] == 0
    assert tel["queue_wait_created_s_total"] == pytest.approx(
        tel["queue_wait_s_total"]
    )


def test_first_admission_stamp_survives_preemption(cfg, params):
    """``first_admitted_at`` is stamped once, at the first admission; a
    preemption re-stamps ``admitted_at`` (time-to-resume) and leaves
    it alone."""
    clock = StepClock()
    eng = _engine(
        cfg, params, clock=clock, kv_layout="paged", kv_page_size=8,
        kv_pages=5, max_seq_len=32, kv_prefix_cache=True,
        kv_preemption=True,
    )
    rng = np.random.default_rng(0)
    handles = [
        eng.submit(list(rng.integers(0, cfg.vocab_size, 6)),
                   max_new_tokens=20)
        for _ in range(4)
    ]
    assert all(eng.request(h).first_admitted_at is None for h in handles)
    first: dict[int, float] = {}
    while eng.has_work:
        clock.advance(0.01)
        eng.step()
        for h in handles:
            req = eng.request(h)
            if req.first_admitted_at is not None:
                assert first.setdefault(h.uid, req.first_admitted_at) == (
                    req.first_admitted_at)
    reqs = [eng.result(h) for h in handles]
    assert [r.first_admitted_at for r in reqs] == [first[h.uid] for h in handles]
    preempted = [r for r in reqs if r.preemptions > 0]
    assert preempted  # the pool genuinely thrashed
    for r in preempted:
        assert r.admitted_at > r.first_admitted_at >= r.created_at
    assert eng.telemetry["prompts_admitted"] == len(handles)


# ------------------------------------------------------ waste counters --


def _count_calls(eng, name):
    """Wrap one of the executor's jitted programs to count its calls."""
    calls = []
    fn = getattr(eng.executor, name)

    def counted(*args):
        calls.append(args)
        return fn(*args)

    setattr(eng.executor, name, counted)
    return calls


def _live(first_pos: int, req) -> int:
    """By hand: a request's decode steps process positions ``first_pos``
    (its first position not written by prefill or extend) up to the
    one before its last token; each reads its position + 1 live KV."""
    last = len(req.prompt) + len(req.generated) - 2
    return sum(p + 1 for p in range(first_pos, last + 1))


@pytest.mark.parametrize("async_loop", [False, True])
@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_waste_counters_equal_hand_counts(cfg, params, layout, async_loop):
    eng = _engine(cfg, params, kv_layout=layout, kv_page_size=8,
                  async_loop=async_loop)
    decodes = _count_calls(eng, "_decode_fn")
    handles = [eng.submit(list(p), max_new_tokens=5 + i)
               for i, p in enumerate(PROMPTS)]
    eng.generate()
    tel, sc = eng.telemetry, eng.serve_cfg
    reqs = [eng.result(h) for h in handles]
    assert decodes and all(len(r.generated) == 5 + i for i, r in enumerate(reqs))
    assert tel["decode_kv_positions_read"] == (
        len(decodes) * sc.decode_steps * sc.max_batch * sc.max_seq_len)
    assert tel["decode_kv_positions_live"] == sum(
        _live(len(r.prompt), r) for r in reqs)
    # every prompt fits the 8-token bucket
    assert tel["prompt_positions_dispatched"] == (
        tel["prefill_dispatches"] * sc.max_batch * 8)
    assert tel["prompt_positions_written"] == sum(len(p) for p in PROMPTS)


@pytest.mark.parametrize("async_loop", [False, True])
def test_waste_counters_leave_out_shared_prefix_pages(cfg, params, async_loop):
    """A prefix hit writes no prompt KV through prefill: its tail past the
    two shared pages rides the decode scan teacher-forced, and those
    steps read live KV like any other."""
    eng = _engine(cfg, params, kv_layout="paged", kv_page_size=8,
                  kv_prefix_cache=True, async_loop=async_loop)
    rng = np.random.default_rng(3)
    shared = list(rng.integers(1, cfg.vocab_size, 16))
    a = eng.submit(shared + [7, 8, 9, 10], max_new_tokens=4)
    eng.generate()
    b = eng.submit(shared + [3, 1, 4, 1, 5], max_new_tokens=5)
    eng.generate()
    tel = eng.telemetry
    assert tel["prefill_tokens_saved"] == 16
    ra, rb = eng.result(a), eng.result(b)
    assert tel["prompt_positions_written"] == 20
    assert tel["prompt_positions_dispatched"] == 2 * 32  # one 32 bucket
    assert tel["decode_kv_positions_live"] == _live(20, ra) + _live(16, rb)


def test_waste_counters_count_extend_windows():
    """Chunked prefill on a datapath that replays through the extend
    program: a 20-token prompt is an 8-token chunk (one 2 x 8 prefill)
    and a 12-token tail (two 2 x 8 extend windows)."""
    from repro.core import precision as P

    kv8 = P.PrecisionPolicy(
        "kv8", (P.Rule("kv_cache", P.int8(per_channel=False)),))
    cfg = configs.get_config("granite-8b", reduced=True)
    eng = _engine(cfg, lm.init_params(cfg, KEY), policy=kv8,
                  prefill_chunk=8)
    prompt = list(np.random.default_rng(0).integers(1, cfg.vocab_size, 20))
    h = eng.submit(prompt, max_new_tokens=5)
    eng.generate()
    tel = eng.telemetry
    assert (tel["prefill_dispatches"], tel["extend_dispatches"]) == (1, 2)
    assert tel["prompt_positions_dispatched"] == 2 * 8 + 2 * (2 * 8)
    assert tel["prompt_positions_written"] == 20
    assert tel["decode_kv_positions_live"] == _live(20, eng.result(h))
