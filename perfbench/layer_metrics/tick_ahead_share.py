"""Share of the window's decode steps that the plain tick launched
while the step before them was still unread, in per cent:
`defer_tick_ahead_total` over `defer_decode_ticks_total`, both as they
moved across the window (`Run.registry_open` and `registry_close`).
None where the program has no such counter (a commit before the
launch ahead) or ran no step in the window."""

AHEAD = 'defer_tick_ahead_total{server="paged"}'
TICKS = 'defer_decode_ticks_total{server="paged"}'


def read(run):
    if AHEAD not in run.registry_close:
        return None
    moved = {
        name: run.registry_close.get(name, 0) - run.registry_open.get(name, 0)
        for name in (AHEAD, TICKS)
    }
    return 100.0 * moved[AHEAD] / moved[TICKS] if moved[TICKS] else None
