"""Kernels: least time for the decode work in the trace (counted by the
architecture's module: weights once a scan step, each token's live KV once,
both bf16) at the cell's chips' peaks over the decode-scan programs' device
time per chip, in % (throughput cells)."""

from bench.readers import decode_roofline as read  # noqa: F401
