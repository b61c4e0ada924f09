"""Median time to first token, in ms, over every request due in the window,
timed from when it was due under the open-loop schedule; a request that
never got a first token counts until the run ended."""

from bench.readers import percentile, ttft_s


def read(rec):
    end = max([t for r in rec.records for t in r.tokens] + [rec.window[1]])
    return 1e3 * percentile([ttft_s(r, end) for r in rec.requests], 50)
