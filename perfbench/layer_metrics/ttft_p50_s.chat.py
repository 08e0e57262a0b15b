"""As the end-to-end ttft_p50_s. In the chat cells it is a per-layer
number: a request waits a uniformly random part of a tick before a
short prefill, so the median is mostly tick phase."""


def read(run):
    return run.ttft_p50()
