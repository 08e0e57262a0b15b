"""Host time from entering the default tick to the step's dispatch
returning: the program's `paged.tick.plan` + `paged.tick.dispatch`
spans, summed per tick; median over the window's ticks."""

from perfbench import program_spans


def read(run):
    return program_spans.phase_p50(run, ("paged.tick.plan", "paged.tick.dispatch"))
