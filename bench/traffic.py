"""The one traffic generator: it reads a mix's parameters (a
``bench/traffic/<name>.json`` file) and turns them, with a seed, into requests.

A mix is open loop (``"loop": "open"``: Poisson arrivals at ``rate_rps``,
timed from when each request was due) or closed loop (``"loop":
"closed"``: ``clients`` callers that each send their next request when
the last one finished).  Prompt and output lengths are lognormal
(``median``, ``sigma``), clipped to ``[min, max]``; a prompt may start
with one of ``tenants`` shared system prompts of ``tokens`` tokens, the
tenant drawn Zipf(``zipf_s``).

Every seed gets the same work in another order: lengths, gaps between
arrivals and tenants are the quantiles (i + 0.5) / n of their
distributions, and the seed only permutes them and draws the token ids.
So runs with different seeds differ by arrangement, not by the amount of
work.  The open-loop schedule is made in segments (lead-in, window,
after), each with its own fixed set, and each segment's gaps are scaled
to span its length exactly.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

#: requests made per closed-loop client (far more than a run finishes)
PER_CLIENT = 128


@dataclasses.dataclass
class Req:
    """One request as the client sends it."""

    prompt: list[int]
    max_new: int
    #: seconds after the schedule's start when it is due (open loop)
    due: float = 0.0
    #: which shared system prompt it starts with (-1: none)
    tenant: int = -1
    client: int = -1


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int, rng) -> np.ndarray:
    """``n`` lognormal lengths at fixed quantiles, clipped, permuted."""
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    x = np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)
    return rng.permutation(x)


def tenants(spec: dict, n: int, rng) -> np.ndarray:
    """``n`` tenant indices whose counts follow Zipf(zipf_s), permuted."""
    k = np.arange(1, spec["tenants"] + 1)
    cdf = np.cumsum(k ** -float(spec["zipf_s"]))
    cdf /= cdf[-1]
    return rng.permutation(np.searchsorted(cdf, _quantiles(n)))


def gaps(n: int, span: float, rng) -> np.ndarray:
    """``n`` exponential gaps at fixed quantiles, permuted, summing to ``span``."""
    g = -np.log1p(-_quantiles(n))
    return rng.permutation(g * (span / g.sum()))


class Mix:
    """A traffic mix bound to a seed and a vocabulary."""

    def __init__(self, spec: dict, seed: int, vocab: int):
        self.spec = spec
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)
        sp = spec.get("shared_prefix")
        self.prefixes = (
            [self._tokens(sp["tokens"]) for _ in range(sp["tenants"])] if sp else []
        )

    @property
    def open(self) -> bool:
        return self.spec["loop"] == "open"

    def _tokens(self, n: int) -> list[int]:
        return self.rng.integers(0, self.vocab, n).tolist()

    def _requests(self, n: int) -> list[Req]:
        """``n`` requests whose (prompt, output, tenant) triples are the
        same for every seed (paired by a draw that depends on ``n`` alone);
        the seed orders them and draws their tokens."""
        sp = self.spec.get("shared_prefix")
        pair = np.random.default_rng(n)
        user = lengths(self.spec["prompt"], n, pair)
        out = lengths(self.spec["output"], n, pair)
        who = tenants(sp, n, pair) if sp else np.full(n, -1)
        reqs = []
        for i in self.rng.permutation(n):
            u, o, t = user[i], out[i], who[i]
            head = self.prefixes[t] if t >= 0 else []
            reqs.append(Req(head + self._tokens(int(u)), int(o), tenant=int(t)))
        return reqs

    def warmup(self, buckets: tuple[int, ...], max_batch: int) -> list[list[Req]]:
        """Rounds of requests, each sent at once and finished before the
        next, that reach every program and shape the mix can use: for
        each prefill bucket its prompts fall in, a prefill of 1 up to
        ``max_batch`` prompts together (the engine samples each group's
        first tokens at the group's own size); then the shortest and the
        longest prompt with their outputs; and for a shared-prefix mix a
        request behind the first one's system prompt."""
        sp, p, o = self.spec.get("shared_prefix"), self.spec["prompt"], self.spec["output"]
        plen = sp["tokens"] if sp else 0
        lo, hi = plen + p["min"], plen + p["max"]
        rounds, below = [], 0
        for b in sorted(buckets):
            n = min(hi, b)
            if n >= max(lo, below + 1):
                rounds += [[Req(self._tokens(n), 1) for _ in range(k)]
                           for k in range(1, max_batch + 1)]
            below = b
        head = self.prefixes[0] if sp else []
        t = 0 if sp else -1
        rounds.append([Req(head + self._tokens(p["min"]), o["min"], tenant=t),
                       Req(head + self._tokens(p["max"]), o["max"], tenant=t)])
        if sp:
            rounds.append([Req(head + self._tokens(p["min"]), o["min"], tenant=t)])
        return rounds

    def schedule(self, lead_in: float, seconds: float, after: float) -> list[Req]:
        """Open loop: requests with due times over lead-in, window and the
        stretch after it, ``rate_rps`` on average in each segment."""
        rate = float(self.spec["rate_rps"])
        reqs, t0 = [], 0.0
        for span in (lead_in, seconds, after):
            n = max(1, math.ceil(rate * span))
            seg = self._requests(n)
            g = gaps(n, span, self.rng)
            dues = t0 + np.cumsum(g) - g
            for r, d in zip(seg, dues):
                r.due = float(d)
            reqs += seg
            t0 += span
        return reqs

    def clients(self) -> list[list[Req]]:
        """Closed loop: each client's requests, in the order it sends them."""
        n = int(self.spec["clients"])
        reqs = self._requests(n * PER_CLIENT)
        for i, r in enumerate(reqs):
            r.client = i % n
        return [reqs[c::n] for c in range(n)]
