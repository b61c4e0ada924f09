"""Data-parallel replica routing: N engines, one front door.

The scale-out story on top of the single-engine stack: a
:class:`ReplicaRouter` owns ``ServeConfig.replicas`` independent
:class:`~repro.serve.api.Engine` instances (each with its own scheduler,
executor, KV pool, and jit caches — len(prefill_buckets)+2 compiled
programs *per replica*) and routes each submitted request to the
least-loaded replica at admission.  Replicas never share request state,
so everything the single engine guarantees (token identity, program
budget, cancel/preemption semantics, the async pipelined loop) holds
per replica unchanged; the router only multiplexes the request
lifecycle API over them:

* :meth:`submit` — least-loaded admission: the replica with the fewest
  open requests (queued + resident) wins, ties broken by replica index,
  so a fixed submission order routes deterministically.
* :meth:`stream` / :meth:`result` / :meth:`cancel` — delegate to the
  owning replica; router handles carry router-level uids (each engine
  mints its own local uids, so TokenEvents are re-stamped on the way
  out).
* :meth:`step` — pump every replica that has work (one engine
  iteration each); :meth:`generate` runs all replicas to idle.
* :attr:`telemetry` — per-replica telemetries plus summed core
  counters, so throughput math over the fleet stays one dict away.

Pumping a single replica's stream advances only that replica — one
slow tenant cannot stall tokens for requests routed elsewhere.
"""

from __future__ import annotations

import dataclasses
from collections.abc import Iterator
from typing import Any, Callable

from repro.configs.base import ModelConfig, ServeConfig
from repro.serve.api import Engine, RequestHandle, TokenEvent
from repro.serve.sampling import SamplingParams
from repro.serve.scheduler import Request, Scheduler

PyTree = Any

#: telemetry counters summed across replicas (the fleet-level view);
#: everything else is reported per replica only
_SUMMED = (
    "tokens_generated",
    "prefill_dispatches",
    "extend_dispatches",
    "decode_dispatches",
    "kv_pool_donated",
    "prompts_admitted",
    "preemptions",
    "deadline_requests",
    "deadline_missed",
    "deadline_dropped",
    "draft_tokens_proposed",
    "draft_tokens_accepted",
    "spec_dispatches",
    "gen_pages_shared",
)


class ReplicaRouter:
    """Front door over ``ServeConfig.replicas`` data-parallel engines.

    Construction mirrors :class:`~repro.serve.api.Engine` — same
    ``(cfg, params, serve_cfg, kernel, seed, scheduler_factory,
    clock)`` signature — and builds one engine per replica from the
    same config (each replica sees ``replicas=1``; the fan-out lives
    here).  ``params`` are shared by reference: replicas on one host
    read the same device arrays, so N replicas cost N KV pools, not N
    copies of the weights.  Every replica gets the same base ``seed``
    with its replica index folded into the dispatch key (see
    ``ModelExecutor``) — collision-free across (seed, replica) pairs,
    unlike additive ``seed + i`` offsets — so unseeded sampled
    (temperature > 0) replicas draw distinct streams; greedy decoding
    and per-request *seeded* streams are key-independent and stay
    bit-identical to a single engine.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params: PyTree,
        serve_cfg: ServeConfig | None = None,
        kernel: dict | None = None,
        seed: int = 0,
        scheduler_factory: Callable[..., Scheduler] | None = None,
        clock: Callable[[], float] | None = None,
        draft: tuple | None = None,
    ):
        sc = serve_cfg or ServeConfig()
        if sc.replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {sc.replicas}")
        per_replica = dataclasses.replace(sc, replicas=1)
        self.serve_cfg = sc
        self.engines = [
            Engine(
                cfg, params, per_replica, kernel=kernel, seed=seed,
                scheduler_factory=scheduler_factory, clock=clock,
                replica=i, draft=draft,
            )
            for i in range(sc.replicas)
        ]
        self._uid = 0
        #: router uid -> (replica index, that replica's local uid)
        self._route: dict[int, tuple[int, int]] = {}

    # --------------------------------------------------------- admission --
    def _load(self, idx: int) -> int:
        """Open requests on replica ``idx``: queued + resident.  The
        admission signal — O(max_batch) per replica, no device sync."""
        eng = self.engines[idx]
        return len(eng.scheduler.queue) + sum(
            s.active for s in eng.executor.slots
        )

    def submit(
        self,
        prompt: list[int],
        params: SamplingParams | None = None,
        **kw,
    ) -> RequestHandle | list[RequestHandle]:
        """Admit to the least-loaded replica (ties -> lowest index) and
        return a router-level handle (a list of them for ``n > 1``
        fan-out — the siblings stay on one replica so their generation
        pages can share)."""
        idx = min(range(len(self.engines)), key=lambda i: (self._load(i), i))
        local = self.engines[idx].submit(prompt, params, **kw)
        locals_ = local if isinstance(local, list) else [local]
        out = []
        for lh in locals_:
            self._uid += 1
            self._route[self._uid] = (idx, lh.uid)
            out.append(RequestHandle(self._uid))
        return out if isinstance(local, list) else out[0]

    def replica_of(self, handle: RequestHandle | int) -> int:
        """Which replica a request was routed to (introspection/tests)."""
        uid = handle.uid if isinstance(handle, RequestHandle) else handle
        return self._route[uid][0]

    def _resolve(self, handle: RequestHandle | int) -> tuple[Engine, int]:
        uid = handle.uid if isinstance(handle, RequestHandle) else handle
        try:
            idx, local = self._route[uid]
        except KeyError:
            raise KeyError(f"unknown request {uid}") from None
        return self.engines[idx], local

    # --------------------------------------------------------- lifecycle --
    def cancel(self, handle: RequestHandle | int) -> bool:
        eng, local = self._resolve(handle)
        return eng.cancel(local)

    def result(self, handle: RequestHandle | int) -> Request | None:
        eng, local = self._resolve(handle)
        return eng.result(local)

    def request(self, handle: RequestHandle | int) -> Request:
        eng, local = self._resolve(handle)
        return eng.request(local)

    def finish_reason(self, handle: RequestHandle | int) -> str | None:
        eng, local = self._resolve(handle)
        return eng.finish_reason(local)

    def stream(self, handle: RequestHandle | int) -> Iterator[TokenEvent]:
        """The owning replica's event stream, re-stamped with the
        router uid.  Pumping it advances that replica only."""
        uid = handle.uid if isinstance(handle, RequestHandle) else handle
        eng, local = self._resolve(uid)
        for ev in eng.stream(local):
            yield dataclasses.replace(ev, uid=uid)

    @property
    def has_work(self) -> bool:
        return any(eng.has_work for eng in self.engines)

    # -------------------------------------------------------------- loop --
    def step(self) -> dict:
        """Pump one engine iteration on every replica that has work;
        returns summed step stats."""
        total: dict = {}
        for eng in self.engines:
            if not eng.has_work:
                continue
            for k, v in eng.step().items():
                total[k] = total.get(k, 0) + v
        return total

    def generate(
        self,
        prompts: list[list[int]] | None = None,
        params: SamplingParams | None = None,
        *,
        max_new_tokens: int = 16,
        eos_id: int | None = None,
        max_steps: int = 10_000,
    ) -> dict[int, Request]:
        """Batch convenience over the fleet: submit ``prompts`` through
        least-loaded admission, run every replica to idle, and return
        finished requests keyed by *router* uid (including requests
        submitted earlier through :meth:`submit`)."""
        if prompts is not None:
            sp = params or SamplingParams(
                max_new_tokens=max_new_tokens, eos_id=eos_id
            )
            for prompt in prompts:
                self.submit(prompt, sp)
        steps = 0
        while self.has_work and steps < max_steps:
            self.step()
            steps += 1
        out: dict[int, Request] = {}
        for uid, (idx, local) in self._route.items():
            req = self.engines[idx].result(local)
            if req is not None:
                out[uid] = req
        return out

    # --------------------------------------------------------- telemetry --
    @property
    def telemetry(self) -> dict:
        """``replicas`` (per-replica dicts, routing loads) plus fleet
        sums of the core counters."""
        per = [eng.telemetry for eng in self.engines]
        tel: dict = {
            "replicas": len(self.engines),
            "replica_telemetry": per,
            "replica_loads": [
                self._load(i) for i in range(len(self.engines))
            ],
        }
        for key in _SUMMED:
            tel[key] = sum(t.get(key, 0) for t in per)
        return tel

    def kv_stats(self) -> list[dict]:
        return [eng.kv_stats() for eng in self.engines]
