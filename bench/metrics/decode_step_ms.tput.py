"""Model step: device ms of the decode-scan programs in the trace per scan
step they ran (throughput cells)."""

from bench.readers import decode_step_ms as read  # noqa: F401
