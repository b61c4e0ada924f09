#!/usr/bin/env python3
"""The engine's own host spans in a profiler trace, the device's idle time
by the innermost host span that covers it, and the engine's waste counters.

The engine writes ``serve.*`` spans (``src/repro/serve/phases.py``) on the
profiler's clock, which the device trace shares: ``serve.step`` around each
``Engine.step``, and inside it ``serve.schedule``, ``serve.host_prep``,
``serve.dispatch``, ``serve.sample`` and ``serve.collect``, each tagged with
the program it serves (``program=prefill|extend|decode|verify``).
``bench/trace.py`` keeps only the benchmark's own ``bench.*`` spans; ``load``
here keeps both, so ``trace.reduce`` labels each idle gap with the innermost
of either kind, and ``idle_by_span`` sums the idle seconds by that label.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s>

makes one traced run of a cell, as ``bench/run.py --trace 1`` does, with the
engine's waste counters also read at the window's and the trace's edges, and
prints one JSON line: the run's result, ``idle_by_span`` (also split by the
program each engine span serves), ``device_idle_in_engine``, the useful
shares of the decode KV reads and of the prompt positions dispatched over
the window, and the tokens per second of the window and of the traced
stretch.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace  # noqa: E402

#: the engine's span names start with this
ENGINE = "serve."
#: engine counters (``Engine.telemetry``) of the work the programs are
#: handed and of the part of it that is live
WASTE = ("decode_kv_positions_read", "decode_kv_positions_live",
         "prompt_positions_dispatched", "prompt_positions_written")


def engine_spans(path: str | Path) -> list[tuple[int, int, str, str | None]]:
    """``(start_ns, end_ns, name, program)`` of every ``serve.*`` span on
    the host planes of an ``.xplane.pb`` file (or its gzip)."""
    from jax.profiler import ProfileData

    path = Path(path)
    if path.suffix == ".gz":
        data = ProfileData.from_serialized_xspace(gzip.decompress(path.read_bytes()))
    else:
        data = ProfileData.from_file(str(path))
    return [(e.start_ns, e.end_ns, e.name, dict(e.stats).get("program"))
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(ENGINE)]


def load(path: str | Path, programs: bool = False) -> dict:
    """``trace.load``'s events, with the engine's spans among the host
    ones; with ``programs``, each named with the program it serves, as
    in ``serve.sample[prefill]``."""
    events = trace.load(path)
    events["host"] += [(s, e, f"{n}[{p}]" if programs and p else n)
                       for s, e, n, p in engine_spans(path)]
    return events


def idle_by_span(events: dict) -> dict[str, float]:
    """Idle seconds of the traced window (averaged over the chips traced)
    by the innermost host span covering each idle gap's middle, ``none``
    where no span covers it; they sum to the window's idle seconds."""
    gaps = trace.reduce(events, top=sys.maxsize)["idle_gaps"]
    by: dict[str, float] = {}
    for label, secs in gaps:
        by[label] = by.get(label, 0.0) + secs / len(events["ops"])
    return by


def idle_in_engine(by_span: dict[str, float], window_s: float) -> float:
    """The share of the window, in %, that the device spent idle under an
    engine span: the time the engine's own host path held the chip
    back."""
    return 100 * sum(s for n, s in by_span.items() if n.startswith(ENGINE)) / window_s


def useful_share(snaps: dict, live: str, total: str, a: str = "w0",
                 b: str = "w1") -> float | None:
    """100 x the rise of counter ``live`` over that of ``total`` between
    the snapshots ``a`` and ``b``; None where ``total`` did not rise."""
    d_total = snaps[b][total] - snaps[a][total]
    return 100 * (snaps[b][live] - snaps[a][live]) / d_total if d_total else None


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()

    from bench import harness

    # the harness reads the counters it lists at the window's and the
    # trace's edges; these ride along for this run
    harness.COUNTERS += WASTE + ("tokens_generated",)
    with tempfile.TemporaryDirectory(prefix="bench-spans-") as tmp:
        kept = Path(tmp) / "trace.xplane.pb"
        result = harness.run_cell(args.workload, args.seed, args.seconds, True, T_START,
                                  keep_trace=str(kept))
        events = load(kept)
        by_program = idle_by_span(load(kept, programs=True))
    reduced = trace.reduce(events)
    by_span = idle_by_span(events)
    snaps = result["diagnostics"]["counters"]
    window_s = reduced["window_s"]
    tokens = {k: snaps[b]["tokens_generated"] - snaps[a]["tokens_generated"]
              for k, a, b in (("window", "w0", "w1"), ("traced", "t0", "t1"))}
    print(json.dumps({
        "result": result,
        "idle_gaps": reduced["idle_gaps"],
        "idle_by_span": by_span,
        "idle_by_span_program": by_program,
        "idle_s": window_s - reduced["busy_s"],
        "window_s": window_s,
        "device_idle_in_engine": idle_in_engine(by_span, window_s),
        "decode_kv_useful_share": useful_share(
            snaps, "decode_kv_positions_live", "decode_kv_positions_read"),
        "prefill_useful_share": useful_share(
            snaps, "prompt_positions_written", "prompt_positions_dispatched"),
        "tokens_per_s": {"window": tokens["window"] / args.seconds,
                         "traced": tokens["traced"] / window_s},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
