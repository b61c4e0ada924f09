"""From a profiler trace to device busy and idle time, per-program device
time, the top device operations and the longest idle gaps.

``load`` reads the ``.xplane.pb`` the JAX profiler writes and keeps three
kinds of event, each as ``(start_ns, end_ns, name)``:

* device operations: the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane;
* program executions: the ``XLA Modules`` line of the same planes;
* the benchmark's own host spans: events named ``bench.*`` on the host
  planes (written with ``jax.profiler.TraceAnnotation``); ``bench.window``
  marks the traced window.

``reduce`` does the arithmetic on those lists alone, so it is tested on a
small trace recorded on the chip.  An operation's event is named by its
HLO instruction (``"%fusion.12 = f32[...] fusion(...)"``), and XLA names an
instruction after its opcode, so the collectives are found by name.
"""

from __future__ import annotations

import gzip
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
WINDOW = "bench.window"
#: program name fragments -> the engine program they stand for
PROGRAMS = {"_decode_scan": "decode", "_prefill_batch": "prefill", "_extend_batch": "extend"}
#: collective operations, as the TPU compiler names them in the engine's
#: programs on a v5e:2x2 mesh: the operations themselves (``%all-gather.151``,
#: ``%all-reduce.9``, ``%all-to-all.7``), their ``-start``/``-done`` halves,
#: and the fusions that start and finish an asynchronous one
#: (``%async-collective-start``, ``%async-collective-done.1``).  A compute
#: fusion that carries an asynchronous collective (``calls=
#: %async_collective_fusion.N``) is compute: its time is the work that
#: overlaps the transfer.
COLLECTIVE = re.compile(r"%(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
                        r"|async-collective-start|async-collective-done)")
#: operations that hold others (a loop's or a call's body runs inside them)
CONTAINER = re.compile(r"%(while|conditional|call)\b")


def load(path: str | Path) -> dict:
    """Events of an ``.xplane.pb`` file (or its gzip, ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData

    path = Path(path)
    if path.suffix == ".gz":
        data = ProfileData.from_serialized_xspace(gzip.decompress(path.read_bytes()))
    else:
        data = ProfileData.from_file(str(path))
    ops: dict[str, list] = {}
    modules: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                dest = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dest is not None:
                    dest.setdefault(plane.name, []).extend(
                        (e.start_ns, e.end_ns, e.name) for e in line.events
                    )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    (e.start_ns, e.end_ns, e.name) for e in line.events
                    if e.name.startswith("bench.")
                )
    return {"ops": ops, "modules": modules, "host": host}


def program_of(name: str) -> str | None:
    for frag, prog in PROGRAMS.items():
        if frag in name:
            return prog
    return None


def _clip(events, lo, hi):
    return [(max(s, lo), min(e, hi), n) for s, e, n in events if e > lo and s < hi]


def _union(events) -> list[tuple[float, float]]:
    spans: list[list[float]] = []
    for s, e, _ in sorted(events):
        if spans and s <= spans[-1][1]:
            spans[-1][1] = max(spans[-1][1], e)
        else:
            spans.append([s, e])
    return [(s, e) for s, e in spans]


def _cut(a, b, keep: bool) -> list[tuple[float, float]]:
    """The parts of the disjoint sorted intervals ``a`` that lie inside
    (``keep``) or outside the disjoint sorted intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        t, k = s, j
        while k < len(b) and b[k][0] < e:
            bs, be = b[k]
            if keep:
                out.append((max(s, bs), min(e, be)))
            elif bs > t:
                out.append((t, bs))
            t, k = max(t, be), k + 1
        if not keep and t < e:
            out.append((t, e))
    return out


def _seconds(spans) -> float:
    return sum(e - s for s, e in spans) / 1e9


def _label(host, t) -> str:
    """The innermost benchmark span (other than the window) covering t."""
    best = None
    for s, e, n in host:
        if n != WINDOW and s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, n)
    return best[2] if best else "none"


def reduce(events: dict, top: int = 10) -> dict:
    """Busy and idle seconds of the traced window (averaged over the chips
    traced), device seconds and executions per engine program, seconds of
    collective operations inside the engine's programs and the part of them
    in which no other operation ran on the chip (both averaged over the
    chips), and the breakdown: top device operations by time, longest idle
    gaps by the host span they fell in."""
    windows = [(s, e) for s, e, n in events["host"] if n == WINDOW]
    if not windows or not events["ops"]:
        raise ValueError("trace has no bench.window span or no device operations")
    lo, hi = windows[0]
    window_s = (hi - lo) / 1e9
    busy, gaps, op_time = [], [], {}
    coll_s = alone_s = 0.0
    for plane, ops in events["ops"].items():
        ops = _clip(ops, lo, hi)
        spans = _union(ops)
        busy.append(_seconds(spans))
        engine = _union(m for m in _clip(events["modules"].get(plane, []), lo, hi)
                        if program_of(m[2]))
        coll = _cut(_union(o for o in ops if COLLECTIVE.match(o[2])), engine, keep=True)
        other = _union(o for o in ops if not COLLECTIVE.match(o[2])
                       and not CONTAINER.match(o[2]))
        coll_s += _seconds(coll)
        alone_s += _seconds(_cut(coll, other, keep=False))
        edges = [lo] + [x for span in spans for x in span] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, (a + b) / 2))
        for s, e, n in ops:
            n = n.split(" = ", 1)[0]  # "%fusion.12 = f32[...] fusion(...)" -> "%fusion.12"
            op_time[n] = op_time.get(n, 0.0) + (e - s) / 1e9
    programs: dict[str, dict] = {}
    for plane, mods in events["modules"].items():
        for s, e, n in _clip(mods, lo, hi):
            prog = program_of(n)
            if prog is None:
                continue
            p = programs.setdefault(prog, {"count": 0, "device_s": 0.0})
            p["count"] += 1
            p["device_s"] += (e - s) / 1e9
    n_chips = len(events["ops"])
    for p in programs.values():
        p["count"] /= n_chips
        p["device_s"] /= n_chips
    gaps.sort(reverse=True)
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy),
        "programs": programs,
        "collective_s": coll_s / n_chips,
        "collective_alone_s": alone_s / n_chips,
        "device_ops": sorted(([n, t] for n, t in op_time.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": [[_label(events["host"], mid), g / 1e9] for g, mid in gaps[:top]],
    }
