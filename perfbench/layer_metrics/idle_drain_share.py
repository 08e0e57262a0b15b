"""Device idle time under the program's `paged.tick.drain` span, over
the traced slice: the part of device_idle_share spent after a tick's
tokens are on the host and before the next tick begins."""

from perfbench import program_spans


def read(run):
    return program_spans.idle_share(run, ("paged.tick.drain",))
