"""Pallas flow-kernel time per training step and chip, ms."""

from bench.lib.readers import flow_kernels_ms as read  # noqa: F401
