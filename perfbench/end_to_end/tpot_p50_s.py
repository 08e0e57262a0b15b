"""Median over requests of seconds per output token, each over the
part of its stream inside the window (at least 8 stamps). Host clock."""

from perfbench import metrics


def read(run):
    per_request = metrics.tpot_per_request(run.window_stamps())
    return metrics.median(per_request) if per_request else None
