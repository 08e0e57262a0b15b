"""Share of the window the loop spent inside `_admit` calls that
seated a request: the time every live slot waited for a prefill."""


def read(run):
    admits = run.window_admits()
    return 100.0 * sum(t1 - t0 for t0, t1, _ in admits) / (run.t_close - run.t_open)
