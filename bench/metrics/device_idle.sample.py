"""Device idle share of a traced sampling window, %."""

from bench.lib.readers import device_idle as read  # noqa: F401
