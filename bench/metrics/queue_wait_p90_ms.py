"""Scheduler: 90th percentile, in ms, of the engine's first admission stamp
minus its creation stamp, over every request due in the window."""

from bench.readers import percentile


def read(rec):
    p = percentile([r.admitted - r.created for r in rec.requests if r.admitted], 90)
    return None if p is None else 1e3 * p
