"""Per-engine-step phase tracing: where does a step's time actually go?

The paper's discipline is *accountable* latency — a 64-cycle MLP is only
meaningful inside its ~140k-cycle shell if you can say where the other
cycles went.  The serving counterpart: each engine step decomposes into

    schedule   policy: FifoScheduler/DeadlineScheduler.schedule()
    host_prep  numpy batch assembly + page-table bookkeeping
               (ensure / flush_copies / write_table) before a dispatch
    dispatch   handing the jitted program to the runtime (returns as
               soon as the computation is enqueued; first-call
               compilation also lands here)
    device     waiting for the dispatched arrays (block_until_ready)
    sample     host-side post-processing: device->host transfers,
               token sampling/routing, slot bookkeeping

:class:`PhaseTracer` accumulates per-phase seconds for the current step,
pushes the finished record into a bounded ring buffer, and summarizes
p50/p95/p99 on demand.  Isolating ``device`` requires *fencing* every
dispatch (``jax.block_until_ready``), which serializes host and device
work — so tracing is **off by default** (``ServeConfig.trace_phases``)
and the off path is :data:`NULL_TRACER`, which keeps no records and
never fences: an untraced engine does no extra device work, and its
only extra host work is the profiler spans below, test-enforced to cost
no measurable throughput.

The fenced tracer *destroys the pipeline it measures*: under the
PR-8 async engine loop (``ServeConfig.async_loop``) a fence between
dispatch N and schedule N+1 is exactly the serialization the loop
exists to remove.  :class:`OverlapTracer`
(``ServeConfig.phase_mode="overlap"``) is the non-fencing alternative:
it records, per step,

    overlap    host seconds between a dispatch returning and its
               collect starting — device compute hidden under host
               work (schedule/host_prep/sample of the next step)
    collect    the residual blocking wait inside ``collect`` — host
               time the device did NOT hide (the pipeline bubble)

and its summary adds ``device_overlap_s`` (total overlap),
``host_bubble_s`` (total collect wait), and ``overlap_efficiency`` =
overlap / (overlap + bubble) — 1.0 means the loop is fully pipelined,
0.0 means it is effectively synchronous.  ``overlap`` is an upper
bound on hidden device time (the device may finish early inside the
span); ``collect`` is exact.

The tracer always stamps with ``time.perf_counter`` — real host/device
seconds — even when the engine itself runs on a virtual clock
(:class:`~repro.serve.workloads.StepClock`): phase timings are physical
measurements, arrival/deadline bookkeeping is simulation time.

Every tracer, :data:`NULL_TRACER` included, also writes each phase as a
``jax.profiler.TraceAnnotation`` span on the profiler's clock, the
clock the device trace shares: ``serve.step`` around a whole engine
step, and inside it ``serve.schedule``, ``serve.host_prep``,
``serve.dispatch``, ``serve.device`` (fenced tracer only: elsewhere
nothing is waited for), ``serve.sample`` and ``serve.collect`` (the
decode scan's blocking device->host transfer, whatever ring key the
tracer files it under).  A span inside a step carries the engine
program it serves as the argument ``program`` (``prefill``,
``extend``, ``decode`` or ``verify``).  A span records nothing unless
a profiler session is active (``jax.profiler.start_trace`` or
``trace``); without one it costs about a microsecond.  So one set of
phases feeds two sinks: any profiler trace, and the opt-in ring.
"""

from __future__ import annotations

import collections
import time

import jax
from jax.profiler import TraceAnnotation

#: phase names in within-step order (``wall`` is the whole step)
PHASES = ("schedule", "host_prep", "dispatch", "device", "sample")
#: profiler span name of each phase, of the decode scan's transfer and
#: of the whole step
SPANS = {name: "serve." + name for name in PHASES + ("collect", "step")}


def _percentile(xs: list[float], q: float) -> float:
    """Nearest-rank percentile of a sorted list (numpy-free: the policy
    layer must not grow device deps for a summary)."""
    if not xs:
        return 0.0
    idx = min(len(xs) - 1, max(0, int(round(q / 100.0 * (len(xs) - 1)))))
    return xs[idx]


def _span(name: str, program: str | None = None):
    """The profiler span of phase ``name`` (it records nothing when no
    profiler session is active)."""
    if program is None:
        return TraceAnnotation(SPANS[name])
    return TraceAnnotation(SPANS[name], program=program)


class _NullCtx:
    """Reusable no-op context manager (one shared instance, no allocation
    per phase on the untraced path)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_CTX = _NullCtx()


class NullTracer:
    """The off switch for the ring: every hook is a no-op, nothing is
    recorded and :meth:`fence` never touches the device, so an untraced
    engine's hot loop runs no extra device work.  Its phases are still
    profiler spans, which record only under an active profiler
    session."""

    enabled = False
    #: record key the executor's ``collect`` phase (the blocking
    #: transfer) is filed under ("sample" keeps the fenced/untraced
    #: record schema; the overlap tracer files it as "collect" — the
    #: pipeline-bubble measurement).  Its span is ``serve.collect``.
    collect_phase = "sample"
    #: phases that write no span: without a fence ``device`` waits for
    #: nothing
    unspanned = frozenset({"device"})

    def begin_step(self) -> None:
        pass

    def end_step(self) -> None:
        pass

    def step(self):
        """Context manager around one engine step: the ``serve.step``
        span."""
        return _span("step")

    def phase(self, name: str, program: str | None = None):
        """Context manager around one phase: its span, tagged with the
        engine ``program`` it serves."""
        if name in self.unspanned:
            return _NULL_CTX
        return _span(name, program)

    def fence(self, value):
        return value

    def mark_dispatch(self) -> float:
        """Timestamp a decode dispatch's return (overlap accounting);
        the no-op tracer never reads a clock."""
        return 0.0

    def collect_begin(self, dispatched_at: float) -> None:
        """Record the dispatch->collect host span as hidden device time
        (overlap accounting); no-op here."""

    def records(self) -> list[dict]:
        return []

    def summary(self) -> dict:
        return {}


#: the shared untraced instance every executor starts with
NULL_TRACER = NullTracer()


class _PhaseCtx:
    """Context manager accumulating elapsed seconds into the tracer's
    current step record under ``name`` (re-entrant per step: repeated
    phases — one per dispatch — sum), inside the phase's span."""

    __slots__ = ("tracer", "name", "span", "t0")

    def __init__(self, tracer: PhaseTracer, name: str, program: str | None):
        self.tracer = tracer
        self.name = tracer.collect_phase if name == "collect" else name
        self.span = (
            _NULL_CTX if name in tracer.unspanned else _span(name, program)
        )

    def __enter__(self):
        self.span.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        cur = self.tracer._cur
        if cur is not None:
            cur[self.name] = (
                cur.get(self.name, 0.0) + time.perf_counter() - self.t0
            )
        self.span.__exit__(*exc)
        return False


class _StepCtx:
    """Context manager around one engine step: the ``serve.step`` span
    and the tracer's step record (kept if the step returned)."""

    __slots__ = ("tracer", "span")

    def __init__(self, tracer: PhaseTracer):
        self.tracer = tracer
        self.span = _span("step")

    def __enter__(self):
        self.span.__enter__()
        self.tracer.begin_step()
        return self

    def __exit__(self, exc_type, *exc):
        if exc_type is None:
            self.tracer.end_step()
        self.span.__exit__(exc_type, *exc)
        return False


class PhaseTracer:
    """Accumulate per-step phase timings into a bounded ring buffer.

    Usage (the engine/executor wiring)::

        with tracer.step():              # begin_step ... end_step
            with tracer.phase("schedule"):
                decision = scheduler.schedule(slots)
            with tracer.phase("dispatch", "decode"):
                out = jitted(...)        # returns once enqueued
            with tracer.phase("device", "decode"):
                tracer.fence(out)        # block_until_ready

    ``fence`` is the only device-touching call and exists so the *same*
    executor source runs fenced and unfenced: under :data:`NULL_TRACER`
    it is a pass-through.
    """

    enabled = True
    #: per-step record keys the summary reports (subclasses extend)
    _names = PHASES
    #: see NullTracer.collect_phase
    collect_phase = "sample"
    #: see NullTracer.unspanned: the fenced tracer spans every phase
    unspanned = frozenset()

    def __init__(self, ring: int = 512):
        if ring < 1:
            raise ValueError(f"phase ring must hold >= 1 record, got {ring}")
        self._ring: collections.deque[dict] = collections.deque(maxlen=ring)
        self._cur: dict | None = None
        self._t0 = 0.0
        #: dispatches fenced so far (the off-costs-nothing guard test
        #: asserts an untraced engine performs zero fences)
        self.fences = 0

    # ------------------------------------------------------------ hooks --
    def begin_step(self) -> None:
        self._cur = {}
        self._t0 = time.perf_counter()

    def end_step(self) -> None:
        if self._cur is None:
            return
        self._cur["wall"] = time.perf_counter() - self._t0
        self._ring.append(self._cur)
        self._cur = None

    def step(self) -> _StepCtx:
        return _StepCtx(self)

    def phase(self, name: str, program: str | None = None) -> _PhaseCtx:
        return _PhaseCtx(self, name, program)

    def fence(self, value):
        """Wait for every array in ``value`` (pytree) to be ready on
        device.  Call inside a ``phase("device")`` block, right after the
        dispatch returned, to split launch time from device time."""
        self.fences += 1
        return jax.block_until_ready(value)

    def mark_dispatch(self) -> float:
        """Timestamp a decode dispatch's return.  The fenced tracer
        already isolates device time via :meth:`fence`; the stamp is
        consumed by :class:`OverlapTracer.collect_begin`."""
        return time.perf_counter()

    def collect_begin(self, dispatched_at: float) -> None:
        """Overlap accounting hook; the fenced tracer measures device
        time by fencing instead, so this records nothing."""

    # ---------------------------------------------------------- reading --
    def records(self) -> list[dict]:
        """Completed per-step records, oldest first (bounded by the ring)."""
        return list(self._ring)

    def summary(self) -> dict:
        """Per-phase p50/p95/p99/mean in milliseconds plus totals, over
        the retained ring.  A phase absent from a step (e.g. no prefill
        that step) does not drag its percentiles toward zero: each
        phase summarizes only the steps it appeared in."""
        recs = self.records()
        out: dict = {"steps": len(recs), "ring": self._ring.maxlen}
        for name in self._names + ("wall",):
            xs = sorted(r[name] for r in recs if name in r)
            if not xs:
                continue
            total = sum(xs)
            out[name] = {
                "n": len(xs),
                "p50_ms": _percentile(xs, 50) * 1e3,
                "p95_ms": _percentile(xs, 95) * 1e3,
                "p99_ms": _percentile(xs, 99) * 1e3,
                "mean_ms": total / len(xs) * 1e3,
                "total_s": total,
            }
        if recs:
            # time the phase model did not attribute (python routing in
            # the engine loop, telemetry merges): honest accounting
            # means the residual is reported, not hidden
            walls = sum(r.get("wall", 0.0) for r in recs)
            attributed = sum(
                v for r in recs
                for k, v in r.items()
                if k != "wall"
            )
            out["unattributed_s"] = max(0.0, walls - attributed)
        return out


class OverlapTracer(PhaseTracer):
    """The non-fencing tracer for the pipelined loop: same per-phase
    accumulation as :class:`PhaseTracer`, but :meth:`fence` is a
    pass-through (device and host stay overlapped) and device time is
    accounted by *span*, not by blocking:

    * ``overlap`` — host seconds between :meth:`mark_dispatch` (a decode
      dispatch returned, device busy) and :meth:`collect_begin` (the
      host finally needs the results).  Under the async loop this span
      contains the *next* step's schedule/host_prep — exactly the work
      the pipeline hides.  Upper bound on hidden device time.
    * ``collect`` — wrapped by the executor around the blocking
      device->host conversion in ``collect()``: host time the device
      did not hide (the pipeline bubble).  Exact.

    The summary adds ``device_overlap_s`` / ``host_bubble_s`` /
    ``overlap_efficiency`` totals over the ring.
    """

    _names = PHASES + ("collect", "overlap")
    collect_phase = "collect"
    unspanned = NullTracer.unspanned

    def fence(self, value):
        """Never blocks — fencing would serialize the pipeline this
        tracer exists to measure.  ``fences`` stays 0."""
        return value

    def collect_begin(self, dispatched_at: float) -> None:
        if self._cur is not None and dispatched_at > 0.0:
            span = max(0.0, time.perf_counter() - dispatched_at)
            self._cur["overlap"] = self._cur.get("overlap", 0.0) + span

    def summary(self) -> dict:
        out = super().summary()
        recs = self.records()
        overlap = sum(r.get("overlap", 0.0) for r in recs)
        bubble = sum(r.get("collect", 0.0) for r in recs)
        out["device_overlap_s"] = overlap
        out["host_bubble_s"] = bubble
        out["overlap_efficiency"] = (
            overlap / (overlap + bubble) if (overlap + bubble) > 0 else 0.0
        )
        return out


def make_tracer(
    trace: bool, ring: int = 512, mode: str = "fenced"
) -> PhaseTracer | NullTracer:
    """The ServeConfig -> tracer factory: a live tracer when tracing is
    requested (``mode`` "fenced" = :class:`PhaseTracer`, "overlap" =
    :class:`OverlapTracer`), the shared no-op otherwise."""
    if not trace:
        return NULL_TRACER
    if mode == "overlap":
        return OverlapTracer(ring=ring)
    if mode == "fenced":
        return PhaseTracer(ring=ring)
    raise ValueError(
        f"phase_mode must be 'fenced' or 'overlap', got {mode!r}"
    )
