"""Whole step: model FLOPs of the prompt and output tokens processed in the
traced window over its seconds times the chip's bf16 peak, in % (beside
decode_roofline.lat, moving the gap between tokens)."""

from bench.readers import mfu as read  # noqa: F401
