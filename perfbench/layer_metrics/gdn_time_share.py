"""Device time of the recurrent layers' kernels (operations whose
name holds `gdn_`: the decode update `gdn_step`) over the device time
of all operations, first chip, in per cent. None where the trace holds
no such operation (a program without the kernel). Device trace."""

from perfbench import xplane

MARKS = ("gdn_",)


def read(run):
    if run.trace is None:
        return None
    if not any(m in name for name, _, _ in run.trace.ops for m in MARKS):
        return None
    return xplane.time_share(run.trace, MARKS)
