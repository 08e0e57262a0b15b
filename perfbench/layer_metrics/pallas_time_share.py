"""Device time of the Pallas kernels (`tpu_custom_call` events) over
the device time of all operations, first chip. Device trace."""

from perfbench import xplane


def read(run):
    if run.trace is None:
        return None
    return xplane.time_share(run.trace, xplane.KERNEL_MARKS)
