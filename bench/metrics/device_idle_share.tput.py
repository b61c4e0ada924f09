"""Device: 1 minus the union of device operation intervals over the traced
window, in % (throughput cells)."""

from bench.readers import idle_share as read  # noqa: F401
