"""Whole step: model FLOPs of the prompt and output tokens processed in the
traced window over its seconds times the chip's bf16 peak, in % (beside
prefill_roofline.lat, moving time to first token)."""

from bench.readers import mfu as read  # noqa: F401
