"""The window cut into slices of about 5 s whose edges are ends of
dispatches; the median of the slices' rates. A steadier statistic
beside the end-to-end tokens_per_s: one host hiccup moves one slice."""

from perfbench import metrics


def read(run):
    rates = metrics.slice_rates(run.dispatches())
    return metrics.median(rates) if rates else None
