"""Kernels: least time for the decode work in the trace over the decode-scan
programs' device time, in % (latency cells)."""

from bench.readers import decode_roofline as read  # noqa: F401
