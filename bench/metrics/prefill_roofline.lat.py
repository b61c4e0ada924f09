"""Kernels: least time for the prompt work whose first token came in the
trace (counted by the architecture's module: tokens the prefix cache did
not serve, no padding, the LM head once a prompt) at the cell's chips'
peaks over the prefill and cache-extend programs'
device time, in %."""

from bench.readers import least_seconds, prefill_work, program


def read(rec):
    n, secs = program(rec, "prefill", "extend")
    flops, nbytes = prefill_work(rec)
    if not n or not secs or flops <= 0:
        return None
    return 100 * least_seconds(rec, flops, nbytes) / secs
