"""Kernels: least time for the decode work in the trace (bench/work.py:
weights once a scan step, each token's live KV once, both bf16) over the
decode-scan programs' device time, in % (throughput cells)."""

from bench.readers import decode_roofline as read  # noqa: F401
