"""Host time after the tokens reach the host: the program's
`paged.tick.drain` span (per-slot eager ops, callbacks, finishes);
median over the window's ticks."""

from perfbench import program_spans


def read(run):
    return program_spans.phase_p50(run, ("paged.tick.drain",))
