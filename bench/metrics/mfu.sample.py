"""Model operations of the sampling requests over the chip's bf16 peak, %."""

from bench.lib.readers import mfu as read  # noqa: F401
