"""Kernels: least time for the prompt work whose first token came in the
trace (bench/work.py: tokens the prefix cache did not serve, no padding,
the LM head once a prompt) over the prefill and cache-extend programs'
device time, in %."""

from bench import work
from bench.readers import prefill_work, program


def read(rec):
    n, secs = program(rec, "prefill", "extend")
    flops, nbytes = prefill_work(rec)
    if not n or not secs or flops <= 0:
        return None
    return 100 * work.least_seconds(flops, nbytes, rec.peak) / secs
