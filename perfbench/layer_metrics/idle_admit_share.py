"""Device idle time under the program's `paged.admit` span (calls that
seated a request), over the traced slice."""

from perfbench import program_spans


def read(run):
    return program_spans.idle_share(run, ("paged.admit",))
