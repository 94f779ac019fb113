"""XLA conv and matmul time (the conditioner and its VJP) per training step and chip, ms."""

from bench.lib.readers import conditioner_ms as read  # noqa: F401
