"""Client: 90th percentile of time to first token, in ms, over every request
due in the window, timed from when each was due under the open-loop
schedule.  The tail beside the median end-to-end metric: at 0.8x the knee
it swings too widely from run to run to be held to a bound."""

from bench.readers import percentile, ttft_s


def read(rec):
    end = max([t for r in rec.records for t in r.tokens] + [rec.window[1]])
    p = percentile([ttft_s(r, end) for r in rec.requests], 90)
    return None if p is None else 1e3 * p
