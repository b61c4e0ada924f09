"""Arithmetic shared by the metric readers in ``bench/metrics``.

A reader gets the run record ``rec`` (see ``bench/harness.py``):
``rec.requests`` are the client's records of the requests due in the window
(open loop) or active in it (closed loop), ``rec.window`` its host-clock
edges, ``rec.counters`` engine counters read at its edges (``w0``, ``w1``)
and at the traced stretch's (``t0``, ``t1``), ``rec.trace`` the reduced
trace (``bench/trace.py``) or None, ``rec.trace_interval`` the traced
stretch on the host clock (after the window), ``rec.arch`` the architecture's
module (``bench/arch``), which counts the work, ``rec.dims`` the configuration's
shapes, ``rec.peak`` one chip's row of ``bench/peaks.json`` and ``rec.chips``
the chips the cell spans.

Shares of a peak are taken against ``rec.chips`` times one chip's peaks, with
device time the per-chip average that ``bench/trace.py`` gives: a cell that
uses each of its chips fully reads 100%.
"""

from __future__ import annotations

import math


def percentile(values, p: float) -> float | None:
    """Nearest-rank percentile: the smallest value with at least p% of the
    values at or below it."""
    vals = sorted(values)
    if not vals:
        return None
    return vals[max(0, math.ceil(p / 100 * len(vals)) - 1)]


def ttft_s(r, end: float) -> float:
    """Time to first token from when the request was due; a request that
    never got one counts until ``end``."""
    return (r.tokens[0] if r.tokens else end) - r.due


def delta(rec, key: str, a: str, b: str) -> float:
    c = rec.counters
    return c[b][key] - c[a][key] if a in c and b in c else 0


def program(rec, *names) -> tuple[int, float]:
    """(executions, device seconds) of engine programs in the trace."""
    progs = rec.trace["programs"] if rec.trace else {}
    n = sum(progs.get(p, {}).get("count", 0) for p in names)
    return n, sum(progs.get(p, {}).get("device_s", 0.0) for p in names)


def decode_tokens(rec) -> list[int]:
    """Positions of the tokens the decode steps produced in the traced
    stretch (a request's first token comes from its prompt's pass)."""
    a, b = rec.trace_interval
    return [len(r.req.prompt) + i - 1 for r in rec.records
            for i, t in enumerate(r.tokens) if i > 0 and a <= t < b]


def first_tokens(rec) -> list:
    """Requests whose first token came in the traced stretch."""
    a, b = rec.trace_interval
    return [r for r in rec.records if r.tokens and a <= r.tokens[0] < b]


def prefill_work(rec) -> tuple[float, float]:
    """(flops, bytes) of the prompts whose first token came in the traced
    stretch, less the tokens the prefix cache served then."""
    firsts = first_tokens(rec)
    saved = delta(rec, "prefill_tokens_saved", "t0", "t1")
    new = max(0, sum(len(r.req.prompt) for r in firsts) - saved)
    attn = [len(r.req.prompt) - (rec.prefix_tokens if r.req.tenant >= 0 else 0)
            for r in firsts]
    n, _ = program(rec, "prefill", "extend")
    return rec.arch.prefill_work(rec.dims, new, attn, n)


def decode_work(rec) -> tuple[float, float]:
    n, _ = program(rec, "decode")
    return rec.arch.decode_work(rec.dims, decode_tokens(rec), n * rec.decode_steps)


def least_seconds(rec, flops: float, nbytes: float) -> float:
    """Roofline time on the cell's chips: the larger of compute time and
    memory time at ``rec.chips`` times one chip's peaks."""
    peak = rec.peak
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"]) / rec.chips


def decode_roofline(rec) -> float | None:
    n, secs = program(rec, "decode")
    if not n or not secs:
        return None
    return 100 * least_seconds(rec, *decode_work(rec)) / secs


def decode_step_ms(rec) -> float | None:
    n, secs = program(rec, "decode")
    return 1e3 * secs / (n * rec.decode_steps) if n else None


def mfu(rec) -> float | None:
    """Model FLOPs of the prompt and output tokens processed in the traced
    window over the window's seconds times the cell's chips' peak."""
    if not rec.trace:
        return None
    flops = prefill_work(rec)[0] + decode_work(rec)[0]
    if flops <= 0:
        return None
    return 100 * flops / (rec.trace["window_s"] * rec.chips * rec.peak["bf16_flops_per_s"])


def idle_share(rec) -> float | None:
    if not rec.trace:
        return None
    return 100 * (1 - rec.trace["busy_s"] / rec.trace["window_s"])
