"""Model operations of the training steps over the chips' bf16 peak, %."""

from bench.lib.readers import mfu as read  # noqa: F401
