"""99th percentile, in ms, of every gap between consecutive tokens of every
request due in the window, each token stamped when the Engine.step that
delivered it returned to the client."""

from bench.readers import percentile


def read(rec):
    gaps = [b - a for r in rec.requests for a, b in zip(r.tokens, r.tokens[1:])]
    p = percentile(gaps, 99)
    return None if p is None else 1e3 * p
