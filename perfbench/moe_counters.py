"""What the two `moe_*` readers share: a counter of the program's expert
layer (`defer_moe_<name>_total{phase="decode",server="paged"}`, live
slots' rows only) over the expert layers computed, between the window's
edges."""

NAME = 'defer_moe_{}_total{{phase="decode",server="paged"}}'


def per_layer_step(run, name: str):
    """The window's move of counter `name` over its move of
    `layer_steps`; None where the registry holds no such counter (a
    program without the expert layer) or no decode step ran."""
    close, open_ = run.registry_close, run.registry_open
    moved, layer_steps = (
        close[k] - open_.get(k, 0) if k in close else None
        for k in (NAME.format(name), NAME.format("layer_steps"))
    )
    if moved is None or not layer_steps:
        return None
    return moved / layer_steps
