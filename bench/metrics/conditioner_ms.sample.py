"""XLA conv and matmul time (the conditioner) per sampling request, ms."""

from bench.lib.readers import conditioner_ms as read  # noqa: F401
