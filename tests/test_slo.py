"""SLO-aware serving tests: the EDF DeadlineScheduler (miss-rate win
over FIFO on a seeded Poisson workload — the PR's acceptance
criterion), past-deadline drop/demote/ignore policies, deadline-aware
preemption victims, the deterministic workload/replay layer
(serve/workloads.py), the jit-budget invariant with the tracer + EDF
live, and the benchmark matrix regression gate."""

import inspect
import math
import types

import jax
import pytest

from repro import configs
from repro.configs.base import ServeConfig
from repro.models import lm
from repro.serve import (
    DeadlineScheduler,
    Engine,
    FifoScheduler,
    StepClock,
    workloads,
)
from repro.serve import slo as slo_mod
from repro.serve.api import FINISH_DEADLINE, NO_TOKEN
from repro.serve.scheduler import ExecutorCaps, Request, Slot

KEY = jax.random.PRNGKey(17)


@pytest.fixture(scope="module")
def cfg():
    return configs.get_config("granite-8b", reduced=True)


@pytest.fixture(scope="module")
def params(cfg):
    return lm.init_params(cfg, KEY)


def _engine(cfg, params, clock=None, **kw):
    base = dict(
        max_batch=2, max_seq_len=64, prefill_buckets=(8, 16, 32),
        decode_steps=2, temperature=0.0,
    )
    base.update(kw)
    return Engine(cfg, params, ServeConfig(**base), clock=clock)


# ----------------------------------------------------------- layering --


def test_slo_and_workloads_modules_are_device_free():
    """New policy-side modules obey the PR-5 contract: importable and
    auditable without jax (workloads uses numpy for seeded draws)."""
    for mod in (slo_mod, workloads):
        src = inspect.getsource(mod)
        assert "import jax" not in src
        assert "jnp." not in src
        assert "jax." not in src


def test_unknown_scheduler_name_rejected(cfg, params):
    with pytest.raises(ValueError, match="edf"):
        Engine(cfg, params, ServeConfig(
            max_batch=2, max_seq_len=64, scheduler="priority"
        ))


# ---------------------------------------------------- EDF vs FIFO win --


def _replay(cfg, params, scheduler, seed=0):
    clock = StepClock()
    eng = _engine(cfg, params, clock=clock, scheduler=scheduler,
                  overdue_policy="drop")
    events = workloads.poisson(
        rate=20.0, n=16, vocab_size=cfg.vocab_size, seed=seed,
        max_new_tokens=8, deadline_s=(1.0, 10.0),
    )
    rep = workloads.replay(eng, events, step_cost=0.2)
    return rep, eng


def test_edf_beats_fifo_on_seeded_poisson_deadlines(cfg, params):
    """Acceptance criterion: at the same offered load (identical seeded
    Poisson arrivals, virtual step cost) the EDF scheduler achieves a
    strictly lower deadline-miss rate than FIFO.  Deterministic by
    construction: StepClock time, temperature 0, fixed seed."""
    fifo, fifo_eng = _replay(cfg, params, "fifo")
    edf, edf_eng = _replay(cfg, params, "edf")
    assert fifo.requests == edf.requests == 16
    assert fifo.deadline_total == edf.deadline_total == 16
    assert fifo.deadline_missed > 0  # the load genuinely pressures FIFO
    assert edf.miss_rate < fifo.miss_rate
    # both engines completed every token's worth of feasible work and
    # the engine-level SLO telemetry agrees with the replay report
    assert fifo_eng.telemetry["deadline_missed"] == fifo.deadline_missed
    assert edf_eng.telemetry["deadline_missed"] == edf.deadline_missed


def test_replay_is_deterministic(cfg, params):
    a, _ = _replay(cfg, params, "edf", seed=3)
    b, _ = _replay(cfg, params, "edf", seed=3)
    da, db = a.as_dict(), b.as_dict()
    # host_wall_s is real elapsed host time — the one legitimately
    # non-deterministic field; everything else is simulation time
    da.pop("host_wall_s"), db.pop("host_wall_s")
    assert da == db
    assert a.per_request == b.per_request


# ------------------------------------------------------ overdue: drop --


def test_deadline_drop_streams_terminal_event(cfg, params):
    """A queued request whose deadline passes is dropped: it finishes
    with finish_reason='deadline' and its stream yields exactly one
    tokenless terminal event (a drop is an answer, not a hang)."""
    clock = StepClock()
    eng = _engine(cfg, params, clock=clock, max_batch=1,
                  scheduler="edf", overdue_policy="drop")
    blocker = eng.submit([1, 2, 3, 4], max_new_tokens=8)
    eng.step()  # blocker becomes resident (max_batch=1: queue blocks)
    victim = eng.submit([5, 6, 7], max_new_tokens=4, deadline_s=0.5)
    clock.advance(1.0)  # sail past the victim's deadline while queued
    eng.step()
    assert eng.finish_reason(victim) == FINISH_DEADLINE
    events = list(eng.stream(victim))
    assert len(events) == 1
    assert events[0].finished
    assert events[0].finish_reason == FINISH_DEADLINE
    assert events[0].token == NO_TOKEN
    assert eng.result(victim).generated == []
    tel = eng.telemetry
    assert tel["deadline_drops"] == 1
    assert tel["deadline_dropped"] == 1
    assert tel["deadline_missed"] >= 1
    # the blocker still completes untouched
    for _ in range(64):
        if not eng.has_work:
            break
        eng.step()
    assert len(eng.result(blocker).generated) == 8


def test_submit_deadline_validation(cfg, params):
    eng = _engine(cfg, params)
    with pytest.raises(ValueError, match="deadline_s"):
        eng.submit([1, 2], deadline_s=0.0)


def test_default_deadline_inherited_from_config(cfg, params):
    clock = StepClock(t0=5.0)
    eng = _engine(cfg, params, clock=clock, deadline_ms=250.0)
    h = eng.submit([1, 2, 3])
    assert eng.request(h).deadline_at == pytest.approx(5.25)
    # explicit per-request deadline overrides the config default
    h2 = eng.submit([1, 2, 3], deadline_s=2.0)
    assert eng.request(h2).deadline_at == pytest.approx(7.0)


# -------------------------------------------- overdue: demote / ignore --


@pytest.mark.parametrize("policy", ["demote", "ignore"])
def test_overdue_non_drop_policies_complete(cfg, params, policy):
    """Under demote/ignore an overdue queued request still runs to
    completion (counted as a miss, never dropped)."""
    clock = StepClock()
    eng = _engine(cfg, params, clock=clock, max_batch=1,
                  scheduler="edf", overdue_policy=policy)
    blocker = eng.submit([1, 2, 3, 4], max_new_tokens=4)
    eng.step()
    overdue = eng.submit([5, 6, 7], max_new_tokens=4, deadline_s=0.5)
    clock.advance(1.0)
    for _ in range(64):
        if not eng.has_work:
            break
        eng.step()
    assert eng.finish_reason(overdue) == "length"
    assert len(eng.result(overdue).generated) == 4
    tel = eng.telemetry
    assert tel["deadline_dropped"] == 0
    assert tel["deadline_missed"] == 1
    assert len(eng.result(blocker).generated) == 4


def _bare_sched(policy="drop", clock=None):
    """A DeadlineScheduler with no cache/slots behind it — scheduling
    against an empty slot list exercises only the queue-policy slice
    (drop/sort/demote happen before the admission loop ever runs)."""
    sc = ServeConfig(max_batch=2, max_seq_len=64, scheduler="edf",
                     overdue_policy=policy)
    caps = ExecutorCaps(
        max_batch=2, max_seq_len=64, decode_steps=1, buckets=(8,),
        bucketable=True, paged=False, bit_exact=True, prefix_cache=False,
    )
    return DeadlineScheduler(sc, caps, None, clock=clock)


def _queue_of(sched):
    for uid, dl in ((1, 1.0), (2, 5.0), (3, 9.0), (4, None)):
        sched.enqueue(Request(uid, [1], 1, None, deadline_at=dl))


def test_demote_orders_overdue_behind_feasible():
    """The demote reorder is pure queue policy: overdue requests land
    behind every still-feasible one, feasible stay EDF-sorted."""
    sched = _bare_sched("demote", clock=StepClock(t0=2.0))
    _queue_of(sched)
    decision = sched.schedule([])
    assert decision.dropped == []
    assert [r.uid for r in sched.queue] == [2, 3, 4, 1]


def test_ignore_keeps_pure_edf_order():
    sched = _bare_sched("ignore", clock=StepClock(t0=2.0))
    _queue_of(sched)
    decision = sched.schedule([])
    assert decision.dropped == []
    assert [r.uid for r in sched.queue] == [1, 2, 3, 4]


def test_drop_removes_only_overdue_from_queue():
    sched = _bare_sched("drop", clock=StepClock(t0=2.0))
    _queue_of(sched)
    decision = sched.schedule([])
    assert [r.uid for r in decision.dropped] == [1]
    assert [r.uid for r in sched.queue] == [2, 3, 4]
    assert sched.stats["deadline_drops"] == 1


def test_bad_overdue_policy_rejected():
    with pytest.raises(ValueError, match="overdue_policy"):
        _bare_sched("defer")


# -------------------------------------------- deadline-aware victims --


def _slot(uid, admit_seq, deadline_at):
    s = Slot(active=True)
    s.request = Request(uid, [1], 1, None, deadline_at=deadline_at)
    s.admit_seq = admit_seq
    return s


def test_edf_pick_victim_prefers_least_urgent():
    """Preemption under EDF evicts the least-urgent resident (deadline-
    less first, then latest deadline), not FIFO's youngest."""
    sched = DeadlineScheduler.__new__(DeadlineScheduler)
    slots = [
        _slot(1, admit_seq=3, deadline_at=1.0),   # most urgent, youngest
        _slot(2, admit_seq=1, deadline_at=9.0),
        _slot(3, admit_seq=2, deadline_at=None),  # deadline-less
    ]
    assert sched._pick_victim([0, 1, 2], slots) == 2
    assert sched._pick_victim([0, 1], slots) == 1
    assert sched._pick_victim([0], slots) == 0
    # FIFO's rule stays youngest-resident
    fifo = FifoScheduler.__new__(FifoScheduler)
    assert fifo._pick_victim([0, 1, 2], slots) == 0


def test_urgency_key():
    assert slo_mod._urgency(Request(1, [1], 1, None)) == math.inf
    assert slo_mod._urgency(Request(1, [1], 1, None, deadline_at=3.0)) == 3.0


# ------------------------------------------------- workloads / traces --


def test_poisson_workload_is_seeded_and_sorted():
    a = workloads.poisson(rate=5.0, n=20, vocab_size=64, seed=9,
                          deadline_s=(0.1, 2.0), shared_prefix=4)
    b = workloads.poisson(rate=5.0, n=20, vocab_size=64, seed=9,
                          deadline_s=(0.1, 2.0), shared_prefix=4)
    assert a == b
    assert all(x.at <= y.at for x, y in zip(a, a[1:]))
    assert all(0.1 <= ev.deadline_s <= 2.0 for ev in a)
    prefix = a[0].prompt[:4]
    assert all(ev.prompt[:4] == prefix for ev in a)
    c = workloads.poisson(rate=5.0, n=20, vocab_size=64, seed=10)
    assert c != a
    assert all(ev.deadline_s is None for ev in c)


def test_synchronous_workload_all_at_zero():
    evs = workloads.synchronous(n=5, vocab_size=32, seed=1)
    assert all(ev.at == 0.0 for ev in evs)


def test_poisson_rejects_bad_rate():
    with pytest.raises(ValueError, match="rate"):
        workloads.poisson(rate=0.0, n=1, vocab_size=8)


def test_trace_roundtrip(tmp_path):
    evs = workloads.poisson(rate=3.0, n=7, vocab_size=32, seed=2,
                            deadline_s=0.5, eos_id=1)
    path = str(tmp_path / "trace.jsonl")
    workloads.save_trace(evs, path)
    assert workloads.load_trace(path) == sorted(evs, key=lambda e: e.at)


def test_trace_bad_record_reports_line(tmp_path):
    path = tmp_path / "bad.jsonl"
    path.write_text('{"at": 0.0, "prompt": [1]}\n{"at": "x", "prompt": 3}\n')
    with pytest.raises(ValueError, match="bad.jsonl:2"):
        workloads.load_trace(str(path))


def test_step_clock_contract():
    clock = StepClock(t0=2.0)
    assert clock() == 2.0
    clock.advance(0.5)
    assert clock() == 2.5
    with pytest.raises(ValueError):
        clock.advance(-1.0)


def test_replay_rejects_step_cost_on_wall_clock(cfg, params):
    eng = _engine(cfg, params)  # default wall clock
    with pytest.raises(ValueError, match="step_cost"):
        workloads.replay(eng, [], step_cost=0.1)


class _NullEngine:
    """Submit-and-forget engine stub (never has work), isolating
    replay's idle-gap clock handling from the model entirely."""

    def __init__(self, clock):
        self.clock = clock
        self._reqs = {}
        self._uid = 0

    @property
    def has_work(self):
        return False

    def submit(self, prompt, *, max_new_tokens=16, eos_id=None,
               deadline_s=None):
        self._uid += 1
        now = self.clock()
        self._reqs[self._uid] = types.SimpleNamespace(
            uid=self._uid, generated=[], preemptions=0, finished_at=now,
            deadline_at=None if deadline_s is None else now + deadline_s,
        )
        return types.SimpleNamespace(uid=self._uid)

    def result(self, handle):
        return self._reqs[handle.uid]

    def finish_reason(self, handle):
        return "length"

    def step(self):  # pragma: no cover - has_work is always False
        raise AssertionError("stub engine has no work to step")


def test_replay_idle_gap_survives_float_cancellation():
    """Regression: on a reused clock far from zero (a second benchmark
    wave), ``at - (clock() - t_start)`` cancels catastrophically and the
    residual gap can round below one ulp of the clock value — advance()
    then no-ops and the idle loop used to spin forever.  With enough
    arrivals, some gap always lands in that window pre-fix."""
    t0 = 1.9861456435215117  # clock value from the wave that hung
    clock = StepClock(t0=t0)
    events = workloads.poisson(rate=200.0, n=256, vocab_size=64, seed=1)
    rep = workloads.replay(_NullEngine(clock), events)
    assert rep.requests == 256
    # the clock crossed every arrival (ulp-nudge error is invisible at
    # any realistic tolerance)
    assert clock() - t0 >= events[-1].at - 1e-9


# ------------------------------------------------ jit budget with SLO --


def test_jit_budget_with_tracer_and_edf(cfg, params):
    """The tracer fences and the EDF policy reorders — neither may mint
    programs: the jit caches stay at len(prefill_buckets) prefill + 1
    decode (+1 extend) exactly as without them (CI-enforced)."""
    clock = StepClock()
    eng = _engine(cfg, params, clock=clock, scheduler="edf",
                  trace_phases=True, kv_layout="paged",
                  kv_prefix_cache=True, kv_preemption=True)
    events = workloads.poisson(
        rate=50.0, n=10, vocab_size=cfg.vocab_size, seed=0,
        max_new_tokens=6, deadline_s=(0.5, 5.0), shared_prefix=8,
    )
    workloads.replay(eng, events, step_cost=0.1)
    assert eng._tracer.fences > 0  # the fenced path was actually live
    tel = eng.telemetry
    buckets = eng.executor.buckets
    assert tel["prefill_compiles"] <= len(buckets)
    assert tel["decode_compiles"] == 1

    def programs(fn):
        size = getattr(fn, "_cache_size", None)
        return size() if callable(size) else 1

    ex = eng.executor
    assert sum(programs(f) for f in ex._prefill_fn.values()) <= len(buckets)
    assert programs(ex._decode_fn) == 1
    if ex._extend_fn is not None:
        assert programs(ex._extend_fn) <= 1


# ------------------------------------------------------- matrix gate --


def test_matrix_check_flags_regressions():
    from benchmarks import matrix

    baseline = {"cells": [
        {"cell": "a/float/paged/none", "us_per_token": 100.0},
        {"cell": "b/float/paged/none", "us_per_token": 200.0},
        {"cell": "only/in/baseline", "us_per_token": 50.0},
    ]}
    fresh = [
        {"cell": "a/float/paged/none", "us_per_token": 115.0},  # +15%: ok
        {"cell": "b/float/paged/none", "us_per_token": 250.0},  # +25%: fail
        {"cell": "only/in/fresh", "us_per_token": 999.0},       # skipped
    ]
    failures = matrix.check(fresh, baseline, tolerance=0.2)
    assert len(failures) == 1
    assert "b/float/paged/none" in failures[0]
    assert matrix.check(fresh, baseline, tolerance=0.5) == []
    assert matrix.check([], baseline) == []


def test_matrix_cells_and_trajectory(tmp_path):
    from benchmarks import matrix

    # cell -> ServeConfig resolution honors each ablation
    full = matrix._serve_cfg(matrix.Cell(), None)
    assert full.kv_layout == "paged" and full.kv_prefix_cache
    assert full.prefill_chunk == 8 and full.cache_extend
    nop = matrix._serve_cfg(matrix.Cell(ablation="no-paging"), None)
    assert nop.kv_layout == "dense" and not nop.kv_prefix_cache
    noc = matrix._serve_cfg(matrix.Cell(ablation="no-chunk"), None)
    assert noc.prefill_chunk is None
    noe = matrix._serve_cfg(matrix.Cell(ablation="no-extend"), None)
    assert not noe.cache_extend
    nopfx = matrix._serve_cfg(matrix.Cell(ablation="no-prefix"), None)
    assert not nopfx.kv_prefix_cache and nopfx.kv_layout == "paged"
    with pytest.raises(ValueError, match="ablation"):
        matrix._serve_cfg(matrix.Cell(ablation="no-such"), None)

    # trajectory is append-only and legacy dicts are migrated
    path = str(tmp_path / "BENCH_matrix.json")
    results = [{"cell": "a/float/paged/none", "us_per_token": 1.0,
                "cached": False}]
    matrix.record(path, "smoke", results)
    matrix.record(path, "smoke", results)
    history = matrix.load_trajectory(path)
    assert len(history) == 2
    assert all(e["bench"] == "matrix" for e in history)
    assert all("date" in e and "git_rev" in e for e in history)
    assert "cached" not in history[0]["cells"][0]


def test_matrix_cache_keyed_on_source_hash(tmp_path, monkeypatch):
    from benchmarks import matrix

    # the key follows file contents, not a git rev
    for sub in ("src", "benchmarks"):
        (tmp_path / sub).mkdir()
        (tmp_path / sub / "m.py").write_text("x = 1\n")
    (tmp_path / "notes.txt").write_text("not code")
    before = matrix.code_hash(str(tmp_path))
    assert matrix.code_hash(str(tmp_path)) == before
    (tmp_path / "notes.txt").write_text("still not code")
    assert matrix.code_hash(str(tmp_path)) == before
    (tmp_path / "src" / "m.py").write_text("x = 2\n")
    assert matrix.code_hash(str(tmp_path)) != before

    # a cell cached under one hash is reused by that hash only
    measured = []
    monkeypatch.setattr(matrix, "CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(
        matrix, "measure_cell",
        lambda cell: measured.append(cell) or {"cell": cell.key},
    )
    cells = (matrix.Cell(),)
    monkeypatch.setattr(matrix, "code_hash", lambda: "aaaa")
    assert [r["cached"] for r in matrix.run_cells(cells)] == [False]
    assert [r["cached"] for r in matrix.run_cells(cells)] == [True]
    monkeypatch.setattr(matrix, "code_hash", lambda: "bbbb")
    assert [r["cached"] for r in matrix.run_cells(cells)] == [False]
    assert len(measured) == 2


def test_serving_trajectory_migrates_legacy_dict(tmp_path):
    import json

    from benchmarks import serving_throughput as bench

    path = tmp_path / "BENCH_serving.json"
    legacy = {"bench": "serving_throughput", "args": {}, "before": [],
              "after": []}
    path.write_text(json.dumps(legacy))
    history = bench.load_trajectory(str(path))
    assert history == [legacy]
    assert bench.load_trajectory(str(tmp_path / "missing.json")) == []
