"""The client side of a run: submits requests to the engine on the traffic's
schedule, pumps ``Engine.step`` in the same process, and stamps every
token with the host clock when the step that delivered it returned.

Host spans ``bench.submit``, ``bench.step`` and ``bench.wait_arrival``
(``jax.profiler.TraceAnnotation``) let a trace say what the host was doing
while the device was idle.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

from jax.profiler import TraceAnnotation

from bench.traffic import Req

clock = time.perf_counter


@dataclasses.dataclass
class Record:
    """What the client saw of one request (host clock, seconds)."""

    req: Req
    due: float
    handle: Any = None
    submitted: float = 0.0
    #: the engine's first admission stamp (a later preemption re-stamps
    #: the engine's copy; this one is kept)
    admitted: float | None = None
    created: float = 0.0
    #: delivery time of each generated token
    tokens: list[float] = dataclasses.field(default_factory=list)
    done: float | None = None
    served: list[int] = dataclasses.field(default_factory=list)


class Client:
    def __init__(self, eng):
        from repro.serve import SamplingParams

        self.eng = eng
        self._params = SamplingParams
        self.records: list[Record] = []
        self.live: list[Record] = []

    def submit(self, req: Req, due: float) -> Record:
        with TraceAnnotation("bench.submit"):
            handle = self.eng.submit(
                req.prompt, self._params(max_new_tokens=req.max_new, temperature=0.0)
            )
        rec = Record(req, due, handle, submitted=clock())
        rec.created = self.eng.request(handle).created_at
        self.records.append(rec)
        self.live.append(rec)
        return rec

    def step(self) -> list[Record]:
        """One engine step; returns the requests it finished."""
        with TraceAnnotation("bench.step"):
            self.eng.step()
        now = clock()
        finished, live = [], []
        for rec in self.live:
            r = self.eng.request(rec.handle)
            new = len(r.generated) - len(rec.tokens)
            if new > 0:
                rec.tokens += [now] * new
            if rec.admitted is None and r.admitted_at:
                rec.admitted = r.admitted_at
            if self.eng.result(rec.handle) is not None:
                rec.done = now
                rec.served = list(r.generated)
                finished.append(rec)
            else:
                live.append(rec)
        self.live = live
        return finished

    def finish_all(self, reqs: list[Req]) -> None:
        """Submit ``reqs`` at once and step until every one has finished."""
        for req in reqs:
            self.submit(req, clock())
        while self.live:
            self.step()


def drive_open(client: Client, schedule: list[Req], base: float,
               window: tuple[float, float], drain_s: float,
               tick: Callable[[float], None], hold: Callable[[], bool]) -> None:
    """Open loop: submit each request when it is due (``base`` + its due
    time), step while there is work, sleep to the next arrival when there
    is none.  Ends once ``hold()`` is false and every request due in the
    window has finished, or ``drain_s`` after the window closed."""
    w0, w1 = window
    i = 0
    while True:
        now = clock()
        tick(now)
        while i < len(schedule) and base + schedule[i].due <= now:
            client.submit(schedule[i], base + schedule[i].due)
            i += 1
        if now >= w1 + drain_s:
            return
        if now >= w1 and not hold():
            if all(r.done is not None for r in client.records if w0 <= r.due < w1):
                return
        if client.eng.has_work:
            client.step()
        elif i < len(schedule):
            with TraceAnnotation("bench.wait_arrival"):
                time.sleep(max(0.0, base + schedule[i].due - clock()))
        else:
            return


def drive_closed(client: Client, queues: list[list[Req]], start: float,
                 window: tuple[float, float], drain_s: float,
                 tick: Callable[[float], None], hold: Callable[[], bool]) -> None:
    """Closed loop: each client sends its next request as soon as its last
    one finished, until the window closes and ``hold()`` is false; then the
    requests in flight drain, for at most ``drain_s`` after the window."""
    w0, w1 = window
    nxt = [0] * len(queues)

    def send(c: int, t: float) -> None:
        client.submit(queues[c][nxt[c] % len(queues[c])], t)
        nxt[c] += 1

    for c in range(len(queues)):
        send(c, start)
    while True:
        now = clock()
        tick(now)
        if now >= w1 + drain_s or (now >= w1 and not hold() and not client.live):
            return
        finished = client.step()
        now = clock()
        if now < w1 or hold():
            for rec in finished:
                send(rec.req.client, now)
