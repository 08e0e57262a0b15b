"""1 - the union of device-operation intervals over the traced
slice, mean over the chips used. Device trace."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
