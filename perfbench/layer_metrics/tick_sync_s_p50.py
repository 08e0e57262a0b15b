"""Host time blocked on the device in a tick: the program's
`paged.tick.sync` span (the `[B]` token transfer); median over the
window's ticks, all of them and not the traced 5 s alone."""

from perfbench import program_spans


def read(run):
    return program_spans.phase_p50(run, ("paged.tick.sync",))
