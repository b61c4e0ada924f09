"""Model step: device ms of the prefill and cache-extend programs in the
trace, per request the engine admitted in the traced stretch."""

from bench.readers import delta, program


def read(rec):
    n, secs = program(rec, "prefill", "extend")
    admitted = delta(rec, "prompts_admitted", "t0", "t1")
    return 1e3 * secs / admitted if n and admitted else None
