"""The training flow steps' least time over the Pallas kernels' time, %."""

from bench.lib.readers import flow_kernels_roofline as read  # noqa: F401
