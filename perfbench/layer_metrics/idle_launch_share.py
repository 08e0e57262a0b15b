"""Device idle time under the program's `paged.tick.plan` and
`paged.tick.dispatch` spans, over the traced slice: the part of
device_idle_share spent before the step reaches the device."""

from perfbench import program_spans


def read(run):
    return program_spans.idle_share(run, ("paged.tick.plan", "paged.tick.dispatch"))
