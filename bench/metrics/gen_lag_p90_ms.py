"""Client: 90th percentile, in ms, of submit time minus due time over every
request due in the window; it tells a starved generator from a slow
engine."""

from bench.readers import percentile


def read(rec):
    p = percentile([r.submitted - r.due for r in rec.requests], 90)
    return None if p is None else 1e3 * p
