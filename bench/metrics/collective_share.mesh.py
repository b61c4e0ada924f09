"""Mesh: device time of the collective operations inside the engine's
programs (bench/trace.py names them: all-gather, all-reduce,
reduce-scatter, collective-permute, all-to-all, their -start/-done halves
and the fusions that start and finish an asynchronous one) over the
engine programs' device time, both per chip averaged, in %."""

from bench.readers import program


def read(rec):
    if not rec.trace or not rec.trace["collective_s"]:
        return None
    _, secs = program(rec, "decode", "prefill", "extend")
    return 100 * rec.trace["collective_s"] / secs if secs else None
