"""defer_host_dispatches_total over defer_tokens_generated_total,
both as they moved inside the window."""


def read(run):
    d = run.counters_close["host_dispatches"] - run.counters_open["host_dispatches"]
    t = run.counters_close["tokens_generated"] - run.counters_open["tokens_generated"]
    return d / t if t else None
